from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh, shard_params  # noqa: F401
