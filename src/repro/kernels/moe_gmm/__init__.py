from repro.kernels.moe_gmm.ops import gmm

__all__ = ["gmm"]
