"""J112 firing: a shard_map body computes a per-shard partial (the mean
of its local batch slice) and returns it through ``out_specs=P()`` —
declared replicated — with no reducing collective. check_vma=False (the
engines' setting, forced by custom_vjp regions) means JAX never checks
the claim: every device silently returns a different loss. This is the
missing-psum / lost-transpose-factor class the fused-xent backward had
to hand-fix."""

RULE = "J112"
EXPECT = "fire"


def build():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.parallel.sharding import shard_map_fn

    mesh = make_mesh(MeshConfig({"data": 2}), jax.devices()[:2])

    def body(xs):
        return jnp.mean(xs)  # per-shard partial, no psum

    fn = jax.jit(shard_map_fn(body, mesh, in_specs=(P("data"),),
                              out_specs=P()))
    return fn, (jnp.ones((8, 4)),)
