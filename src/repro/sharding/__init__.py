from repro.sharding.rules import (  # noqa: F401
    batch_pspecs,
    cache_pspecs,
    client_stack_pspecs,
    flat_pspecs,
    mesh_client_shards,
    param_pspecs,
    sampler_pspecs,
    seed_axes_for,
    seed_pspecs,
    serve_batch_pspecs,
)
