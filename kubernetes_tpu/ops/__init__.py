"""TPU kernels: feasibility masks, scoring, batched assignment."""

from ..utils.platform import configure_compile_cache

configure_compile_cache()

from .backend import TPUBatchBackend
from .batch_kernel import ScanState, StaticArrays, schedule_batch_arrays
