from . import ops, ref
from .kernel import mla_decode_fwd
from .ops import mla_decode_pallas
from .ref import mla_decode_ref

__all__ = ["ops", "ref", "mla_decode_fwd", "mla_decode_pallas", "mla_decode_ref"]
