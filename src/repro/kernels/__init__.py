from . import ops
from .gf_solve import gf_gauss_inverse, gf_solve
from .ntt import ntt_xla
from .ntt_encode import NTTEncodeParams, ntt_encode
from .ops import gf_matmul, ntt, ntt_auto
from .ref import gf_matmul_ref

__all__ = ["gf_matmul", "gf_gauss_inverse", "gf_solve", "gf_matmul_ref",
           "ntt", "ntt_auto", "ntt_xla", "NTTEncodeParams", "ntt_encode",
           "ops"]
