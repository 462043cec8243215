"""granite-moe: the same homogeneous attention + MoE block as OLMoE
(``olmoe.py``); Granite's multipliers are not run (the configuration
lists them under ``departures``)."""
from perfbench.archs.olmoe import (check, experts, forward,  # noqa: F401
                                   kernel_work, make_weights, param_count,
                                   program_config)
