"""The device programs of each step family at small sizes, for
``test_device_phases.py``: ``name -> (jitted program, arguments)``.
Built from the public factories alone."""

import jax
import jax.numpy as jnp
import numpy as np

import windflow_tpu as wf
from windflow_tpu import batch as wbatch
from windflow_tpu.fusion.executor import build_prelude
from windflow_tpu.parallel import mesh as M
from windflow_tpu.windows import ffat_kernels as fk
from windflow_tpu.windows import session_kernels as sk

B, K = 256, 16
ADD = lambda a, b: a + b  # noqa: E731


def _batch(seed=0, keys=K, span=4000):
    r = np.random.default_rng(seed)
    payload = {"k": jnp.asarray(r.integers(0, keys, B), jnp.int32),
               "v": jnp.asarray(r.integers(1, 9, B), jnp.float32)}
    ts = jnp.asarray(np.sort(r.integers(0, span, B)), jnp.int64)
    return payload, ts, jnp.asarray(r.random(B) < 0.9)


def cb(monoid=None, grouping="rank_scatter"):
    step = fk.make_ffat_step(B, K, 2, 4, 2, lambda e: e["v"], ADD,
                             lambda e: e["k"], monoid=monoid,
                             grouping=grouping)
    state = fk.make_ffat_state(jnp.zeros((), jnp.float32), K, 4)
    return jax.jit(step), (state, *_batch())


def tb(monoid, keys=K, NP=8, lift=lambda e: e["v"], zero=jnp.float32):
    step = fk.make_ffat_tb_step(B, keys, 1000, 2, 1, NP, lift, ADD,
                                lambda e: e["k"], monoid=monoid)
    state = fk.make_ffat_tb_state(jnp.zeros((), zero), keys, NP)
    return jax.jit(step), (state, *_batch(keys=keys), jnp.int64(2500))


def tb_scatter():
    """A grid past ``DENSE_PLACE_MAX_CELLS``: the narrow placement
    beside the wide one under a ``lax.cond``, an int64 sum as limbs."""
    return tb("sum", keys=8192, NP=66, lift=lambda e: jnp.int64(1),
              zero=jnp.int64)


def session():
    step = sk.make_session_step(B, 64, 1000, lambda e: jnp.int64(1), ADD,
                                lambda e: e["k"])
    state = sk.make_session_state(jnp.zeros((), jnp.int64), 64)
    return jax.jit(step), (state, *_batch(keys=64), jnp.int64(2500))


def count_ordered():
    """The count window in event-time order: a sort, then the released
    rows a chunk at a time."""
    from windflow_tpu.windows import count_ordered_kernels as ck
    step = ck.make_count_ordered_step(B, 64, 4, 1, lambda e: e["v"], ADD,
                                      lambda e: e["k"], None, True)
    payload, ts, valid = _batch(keys=64)
    one = {"k": jnp.zeros((), jnp.int32), "v": jnp.zeros((), jnp.float32)}
    state = ck.make_count_ordered_state(one, jnp.zeros((), jnp.float32),
                                        64, 4, B)
    return jax.jit(step), (state, payload, ts, valid, jnp.int64(2500))


def mesh_cb():
    """The key shard's count-window step on a host mesh of four."""
    mesh = M.make_mesh(4)
    step = M.make_sharded_ffat_step(mesh, B, K, 2, 4, 2, lambda e: e["v"],
                                    ADD, lambda e: e["k"])
    state = M.make_sharded_ffat_state(jnp.zeros((), jnp.float32), K, 4,
                                      mesh)
    payload, ts, valid = jax.device_put(_batch(), M.batch_sharding(mesh))
    return step._jit, (state, payload, ts, valid)


def unpack():
    dtypes = ("int32", "float32", "int64")
    words = sum(2 if np.dtype(d).itemsize == 8 else 1
                for d in dtypes + ("int64",))
    fn = wbatch.unpack_body(dtypes, B)
    buf = np.arange(words * B + 1, dtype=np.uint32)
    buf[-1] = B - 7
    return jax.jit(fn), (jnp.asarray(buf),)


def chain(window="cb"):
    """Map + Filter fused ahead of a window, as ``fusion.apply_fusion``
    installs them."""
    ma = (wf.MapTPU_Builder(lambda t: {"k": t["k"], "v": t["v"] * 2.0})
          .withName("ma").build())
    fb = (wf.FilterTPU_Builder(lambda t: (t["k"] & 1) == 0)
          .withName("fb").build())
    w = wf.Ffat_WindowsTPU_Builder(lambda t: t["v"], ADD)
    w = (w.withCBWindows(8, 4) if window == "cb"
         else w.withTBWindows(2000, 1000))
    w = w.withKeyBy(lambda t: t["k"]).withMaxKeys(K).withName("win").build()
    w._fused_prelude = build_prelude([ma, fb])[0]
    w._fused_name = "ma|fb|win"
    w.config = wf.Config()
    payload, ts, valid = _batch()
    if window == "cb":
        state = fk.make_ffat_state(jnp.zeros((), jnp.float32), K, w.R)
        args = (state, payload, ts, valid)
    else:
        w.NP = 8
        state = fk.make_ffat_tb_state(jnp.zeros((), jnp.float32), K, 8)
        args = (state, payload, ts, valid, jnp.int64(2))
    return w._build_step(B)._jit, args


def rolling():
    """The rolling aggregate: a sort a distinct group, the sets tested
    and set, one row a touched group."""
    from windflow_tpu.windows import rolling_kernels as rk
    groups = [rk.DistinctGroup(("who", "low"), 40),
              rk.DistinctGroup(("what",), 9)]
    plain = {"n": "sum", "top": "max"}

    def lift(e, ts):
        who = e["v"].astype(jnp.int32)
        return {"n": jnp.int32(1), "top": e["v"], "who": who,
                "low": jnp.where(who < 4, who, -1),
                "what": (ts % 9).astype(jnp.int32)}
    step = rk.make_rolling_step(B, K, lift, plain, groups, lambda e: e["k"])
    one = {"k": jax.ShapeDtypeStruct((), jnp.int32),
           "v": jax.ShapeDtypeStruct((), jnp.float32)}
    spec = jax.eval_shape(lift, one, jax.ShapeDtypeStruct((), jnp.int64))
    state = rk.make_rolling_state(spec, plain, groups, K)
    return jax.jit(step), (state, *_batch(), jnp.int64(2500))


FAMILIES = {
    "cb": cb,
    "cb_sum": lambda: cb("sum"),
    "cb_argsort": lambda: cb(grouping="argsort"),
    "tb_dense": lambda: tb("sum"),
    "tb_scatter": tb_scatter,
    "tb_generic": lambda: tb(None),
    "session": session,
    "count_ordered": count_ordered,
    "rolling": rolling,
    "mesh_cb": mesh_cb,
    "unpack": unpack,
    "chain_cb": chain,
    "chain_tb": lambda: chain("tb"),
}
