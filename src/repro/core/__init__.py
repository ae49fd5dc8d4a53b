"""Core: the paper's contribution — communication-optimal distributed
sketching with random dense matrices, and Nyström approximation."""
from . import rng, lower_bounds, grid, sketch, nystrom  # noqa: F401

from .lower_bounds import (  # noqa: F401
    matmul_lower_bound, matmul_access_lower_bound, matmul_regime,
    nystrom_lower_bound, nystrom_access_lower_bound, nystrom_regime,
    gemm_lower_bound, report_matmul, report_nystrom,
)
from .grid import (  # noqa: F401
    select_matmul_grid, select_nystrom_grids,
    alg1_bandwidth_words, alg2_bandwidth_words,
    alg2_two_grid_executable, select_two_grid_executable,
    two_grid_axis_split, two_grid_shared_mesh,
)
from .sketch import (  # noqa: F401
    DENSE_KINDS, SPARSE_KINDS, VALID_KINDS,
    rand_matmul, rand_matmul_auto, rand_matmul_communicating,
    sketch_reference, sketch_sparse_apply, sparse_omega_map,
    sparse_omega_rows, omega_tile, make_grid_mesh,
    validate_kind,
)
from .rng import seed_keys  # noqa: F401
from .nystrom import (  # noqa: F401
    nystrom_reference, nystrom_no_redist, nystrom_redist, nystrom_general,
    nystrom_two_grid, nystrom_two_grid_fused, nystrom_auto,
    nystrom_second_stage_no_redist, nystrom_second_stage_redist,
    nystrom_second_stage_two_grid, nystrom_second_stage_two_grid_fused,
    reconstruct, relative_error,
)
