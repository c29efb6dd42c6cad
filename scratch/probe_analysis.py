"""Introspection probes behind hetu_tpu/analysis (jax 0.4.37 facts).

Run standalone; each section prints the fact the analyzer relies on:

1. collective primitive names in the jaxpr: psum / all_gather /
   all_to_all / reduce_scatter; shard_map carries params['jaxpr'] (raw
   Jaxpr) + params['mesh'] (axis sizes); pmean lowers to psum + div.
2. jax.named_scope lands on eqn.source_info.name_stack (comm_tag
   attribution channel) and source_info_util.user_frame gives file:line.
3. scan carries params['length'] (trip-count factor) and a ClosedJaxpr.
4. donation is visible as Lowered.args_info leaves (.donated) and as
   `tf.aliasing_output` in the StableHLO text.
5. GSPMD-inserted reshards (with_sharding_constraint -> all-gather) are
   ABSENT from lowered StableHLO and PRESENT in compiled post-SPMD HLO —
   the implicit-reshard rule diffs the two.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

mesh = Mesh(np.array(jax.devices()[:8]), ("dp",))


def f(x, y):
    with jax.named_scope("grad_comm/bucket0"):
        s = lax.psum(x, "dp")
    g = lax.all_gather(y, "dp", axis=0, tiled=True)
    a2a = lax.all_to_all(x.reshape(8, -1), "dp", split_axis=0,
                         concat_axis=0, tiled=False)
    rs = lax.psum_scatter(x, "dp", scatter_dimension=0, tiled=True)
    red = lax.pmean(jnp.sum(x), "dp")
    return s, g, a2a, rs, red


sm = shard_map(f, mesh=mesh, in_specs=(P(), P()),
               out_specs=(P(), P(), P(None), P(), P()), check_vma=False)
cj = jax.make_jaxpr(sm)(np.ones((64,), np.float32),
                        np.ones((4,), np.float32))
(smeqn,) = [e for e in cj.jaxpr.eqns if e.primitive.name == "shard_map"]
print("[1] shard_map mesh:", dict(smeqn.params["mesh"].shape))
for ie in smeqn.params["jaxpr"].eqns:
    print("   ", ie.primitive.name, "| ns:", str(ie.source_info.name_stack))

gj = jax.jit(lambda a, b: (a + b, b * 2), donate_argnums=(0,))
low = gj.lower(np.ones((8,), np.float32), np.ones((8,), np.float32))
print("[4] args_info donated:",
      [l.donated for l in jax.tree_util.tree_leaves(low.args_info)])
print("[4] aliasing in text:", "tf.aliasing_output" in low.as_text())


def g(x):
    x = lax.with_sharding_constraint(x, NamedSharding(mesh, P("dp", None)))
    h = x * 2.0
    h = lax.with_sharding_constraint(h, NamedSharding(mesh, P()))
    return h.sum()


low2 = jax.jit(g).lower(jax.ShapeDtypeStruct((16, 8), np.float32))
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from hetu_tpu.parallel.dstates import count_hlo_collectives  # noqa: E402

print("[5] lowered:", count_hlo_collectives(low2.as_text()))
print("[5] compiled:", count_hlo_collectives(low2.compile().as_text()))
