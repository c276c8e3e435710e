from repro.kernels.csr_gather_reduce import ops, ref  # noqa: F401
from repro.kernels.csr_gather_reduce.kernel import (  # noqa: F401
    gather_reduce_cores_pallas,
    gather_reduce_pallas,
)
from repro.kernels.csr_gather_reduce.ops import (  # noqa: F401
    TileLayout,
    choose_src_bits,
    combine_split_rows,
    gather_reduce,
    pack_edge_words,
    prepare_tiles,
    segment_reduce_rows,
    stack_fold_arrays,
    stack_packed_tiles,
)
