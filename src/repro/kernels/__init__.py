"""The fused WLSH scan kernels and their oracles.

``fused_query`` holds the two Pallas kernels of the query engine's scan
(pass-1 level histograms, pass-2 stop-masked scores); ``ops`` pads and
dispatches one scan block to them or to their fused XLA composite, by
backend (``platform.scan_mode``); ``ref`` holds the pure-jnp oracles the
kernels are tested against, and the hash the build and query encode run.
"""

from .ops import fused_query_block
from .platform import scan_mode

__all__ = ["fused_query_block", "scan_mode"]
