"""Device operations that only communicate: XLA's collectives and the
program's data-movement kernels (``full_mesh_push`` all-gather, barriers)."""

PATTERN = (r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
           r"all-to-all|barrier|all_gather|allgather|full_mesh)")
