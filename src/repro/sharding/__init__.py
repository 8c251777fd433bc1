from repro.sharding.rules import batch_spec, cache_specs, param_specs, MeshInfo

__all__ = ["batch_spec", "cache_specs", "param_specs", "MeshInfo"]
