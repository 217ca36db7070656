"""Smith-Waterman/Gotoh as two Pallas TPU kernels, G = min(8, B) pairs a
program, one pair per sublane.

``gotoh_forward_pallas`` (``sw_kernel``) fills the DP by row blocks with
the score rows resident in VMEM and writes one packed int8 direction row
per DP row to HBM: a (B, n_pad, Mp) matrix. ``sw_traceback_pallas``
(``traceback_kernel``) walks that matrix back from each pair's start
cell: the matrix stays in HBM, each pair holds a double-buffered window
of R full-width rows in VMEM (R from the shapes under a VMEM budget, the
next window DMA'd while the walk is in the current one), each step reads
one byte per pair with an aligned tile load and a sublane/lane select,
and the walk stops when every pair of the group is done. It emits one
state code per alignment column; ``core.pairwise.rows_from_codes`` turns
the codes into the gapped rows. The two kernels are separate jitted
functions, so a device trace names them apart.
"""
from .ops import gotoh_forward_pallas, sw_traceback_pallas  # noqa: F401
from . import ref  # noqa: F401
