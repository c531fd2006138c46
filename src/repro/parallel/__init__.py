from .shard_columns import column_launch, pick_shard_axis  # noqa: F401
from .sharding import (  # noqa: F401
    LOGICAL_RULES,
    ParamSpec,
    logical_sharding,
    sharded_struct,
    specs_to_shardings,
    specs_to_structs,
    pad_to_multiple,
)
