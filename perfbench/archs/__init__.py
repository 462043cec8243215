"""Architecture modules: one file per ``model_type``.

A configuration file names its architecture by ``model_type``, and
``perfbench.lib.model.load_config`` loads ``<model_type>.py`` from this
directory by file path, so a new architecture is one new file here.
Each module holds everything that depends on the architecture's shape
keys, as functions of the configuration dict ``cj``:

- ``check(cj)``: raises on a missing key or a value the module does not run.
- ``program_config(cj)``: the program's ``ModelConfig``.
- ``make_weights(cj, seed)``: seeded weights in the program's parameter
  tree, made on the device in one jitted call.
- ``param_count(cj)``: parameters by part, with at least ``total``,
  ``active`` (what one token multiplies through) and ``head`` (the part
  of ``active`` that a prefill charges once, for its last position).
- ``forward(cj, params, tokens, quant="")``: the plain reference's
  (T, V) logits in float32 at HIGHEST precision, or a control's
  (``perfbench.lib.reference.CONTROLS``) where ``quant`` names one.
- ``kernel_work(cj, kernel, tokens)``: the ``perfbench.lib.work.Work``
  of ``kernel`` for ``tokens`` tokens, summed over exactly the layers
  that run it, each at its own window and expert count; ``work.ZERO``
  for a kernel the architecture does not run.
- ``experts(cj)``: ``(held, top_k)``, the routed experts held per MoE
  layer and the experts each token is sent to.
"""
