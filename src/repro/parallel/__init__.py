from repro.parallel.rules import (  # noqa: F401
    DEFAULT_RULES,
    constraint,
    make_mesh,
    named_sharding,
    partition_spec,
    use_mesh_rules,
)
