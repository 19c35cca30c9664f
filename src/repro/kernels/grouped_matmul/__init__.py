from repro.kernels.grouped_matmul.kernel import NAME, moe_gmm_pallas
from repro.kernels.grouped_matmul.ops import TILE_M, moe_gmm
from repro.kernels.grouped_matmul.ref import gmm_ref

__all__ = ["NAME", "TILE_M", "moe_gmm", "moe_gmm_pallas", "gmm_ref"]
