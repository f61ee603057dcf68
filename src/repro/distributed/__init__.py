from repro.distributed.sharded_search import (  # noqa: F401
    ShardedIndexSpecs,
    build_sharded_arrays,
    distributed_search,
    make_distributed_continue,
    make_distributed_probe,
    make_distributed_search,
    shard_entry_points,
    shard_medoids,
    sharded_index_specs,
)
