"""Device phases (``recorder.phase`` / ``recorder.operator_scope``): every
device program says, in the HLO ``op_name`` of its operations, which phase
of which operator they belong to, and saying so changes nothing that is
computed.  All on the CPU backend's compiled HLO; nothing is timed."""

import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for p in (ROOT, os.path.dirname(__file__)):
    if p not in sys.path:
        sys.path.insert(0, p)

import windflow_tpu as wf  # noqa: E402
from windflow_tpu import batch as wbatch  # noqa: E402
from windflow_tpu.monitoring import recorder  # noqa: E402

import _device_programs as programs  # noqa: E402

PHASE = re.compile(r"(?:^|/)(wf\.(?!op\.)[A-Za-z0-9_.]+)(?=/|$)")
OPERATOR = re.compile(r"(?:^|/)wf\.op\.([^/]+)")
#: instructions that do the work of a step: each lies under a phase
HEAVY = ("gather", "scatter", "sort", "dot", "custom-call", "all-gather",
         "all-reduce", "all-to-all")
#: ... and those that only hold others: under a phase, or (a container
#: whose bodies open phases of their own) under none
CONTAINERS = ("while", "conditional")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (" + "|".join(HEAVY + CONTAINERS)
    + r")\(")

#: family -> (phases its program must hold, its operators, the containers
#: it leaves under no phase)
EXPECT = {
    "cb": ({"wf.group", "wf.place", "wf.fire", "wf.ring"}, set(),
           0),
    "cb_sum": ({"wf.group", "wf.place", "wf.fire", "wf.ring"},
               set(), 0),
    "cb_argsort": ({"wf.group", "wf.place", "wf.fire", "wf.ring"}, set(),
                   0),
    "tb_dense": ({"wf.place", "wf.fire", "wf.ring"}, set(), 0),
    "tb_scatter": ({"wf.place", "wf.fire", "wf.ring"}, set(), 0),
    # the cond that skips an empty batch's placement holds wf.group and
    # wf.place; the cond on the batch's time span holds the session's sort
    "tb_generic": ({"wf.group", "wf.place", "wf.fire", "wf.ring"},
                   set(), 1),
    "session": ({"wf.session.sort", "wf.session.scan",
                 "wf.session.carry", "wf.session.close"}, set(), 1),
    # the loop over the chunks of released rows holds place / fire / ring
    "count_ordered": ({"wf.order", "wf.place", "wf.fire", "wf.ring"},
                      set(), 1),
    "rolling": ({"wf.agg.sort", "wf.agg.distinct", "wf.agg.fold",
                 "wf.agg.rows"}, set(), 0),
    # the rounds' loop holds the owned-lane step and all its phases
    "mesh_cb": ({"wf.mesh.own", "wf.group", "wf.place", "wf.fire",
                 "wf.ring"}, {"mesh.ffat_step"}, 1),
    "unpack": ({"wf.unpack"}, set(), 0),
    "chain_cb": ({"wf.group", "wf.place", "wf.fire", "wf.ring"},
                 {"ma", "fb", "win"}, 0),
    "chain_tb": ({"wf.group", "wf.place", "wf.fire", "wf.ring"},
                 {"ma", "fb", "win"}, 1),
}


def strip_metadata(hlo: str) -> str:
    """Optimized HLO text without what only names things: the
    ``metadata={...}`` of every instruction and the module's source
    tables."""
    out, i = [], 0
    mark = ", metadata={"
    while True:
        j = hlo.find(mark, i)
        if j < 0:
            out.append(hlo[i:])
            break
        out.append(hlo[i:j])
        k, depth, quoted = j + len(mark), 1, False
        while depth:
            c = hlo[k]
            if c == '"' and hlo[k - 1] != "\\":
                quoted = not quoted
            elif not quoted:
                depth += (c == "{") - (c == "}")
            k += 1
        i = k
    return "\n".join(
        line for line in "".join(out).split("\n")
        if not re.match(r"^(\d+ |FileNames|FunctionNames|FileLocations"
                        r"|StackFrames)", line))


@functools.lru_cache(maxsize=None)
def compiled(family: str):
    """``(optimized HLO text, results)`` of a family's program."""
    fn, args = programs.FAMILIES[family]()
    text = fn.lower(*args).compile().as_text()
    return text, [np.asarray(x) for x in jax.tree.leaves(fn(*args))]


def op_names(hlo: str):
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("family", sorted(EXPECT))
def test_compiled_program_names_its_phases(family):
    phases, operators, free_containers = EXPECT[family]
    hlo, _ = compiled(family)
    names = op_names(hlo)
    found = {p for n in names for p in PHASE.findall(n)}
    assert phases <= found, phases - found
    assert found <= set(recorder.PHASES)
    assert {o for n in names for o in OPERATOR.findall(n)} == operators
    # one operator (who) and, innermost, one phase (what) on any path
    assert all(len(PHASE.findall(n)) <= 1 and len(OPERATOR.findall(n)) <= 1
               for n in names)
    assert all(n.index("wf.op.") < n.index(PHASE.findall(n)[0])
               for n in names if OPERATOR.findall(n) and PHASE.findall(n))
    unscoped = []
    for line in hlo.split("\n"):
        m = INSTRUCTION.match(line)
        if m and not PHASE.findall((op_names(line) or [""])[0]):
            unscoped.append((m.group(1), (op_names(line) or [""])[0]))
    assert [u for u in unscoped if u[0] in HEAVY] == []
    assert len(unscoped) == free_containers, unscoped


@pytest.mark.parametrize("family", sorted(EXPECT))
def test_scopes_change_neither_the_program_nor_its_results(family,
                                                           monkeypatch):
    hlo, results = compiled(family)
    # the same program with every scope a no-op
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fn, args = programs.FAMILIES[family]()
    bare = fn.lower(*args).compile().as_text()
    assert not any("wf." in n for n in op_names(bare))
    assert strip_metadata(bare) == strip_metadata(hlo)
    got = [np.asarray(x) for x in jax.tree.leaves(fn(*args))]
    assert len(got) == len(results)
    for a, b in zip(got, results):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_the_names_survive_the_benchmarks_cache_setup(monkeypatch, tmp_path):
    """``setup_compile_cache`` keeps one traceback frame a location; with
    ``jax_include_full_tracebacks_in_locations`` off (as it was set until
    PR 34) this jax names an operation by its primitive alone and no scope
    reaches the HLO: found on the chip, where every phase read nothing."""
    from windflow_tpu.compile_cache import setup_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keep = (jax.config.jax_include_full_tracebacks_in_locations,
            jax.config.jax_traceback_in_locations_limit)
    try:
        setup_compile_cache()
        fn, args = programs.FAMILIES["unpack"]()
        names = op_names(fn.lower(*args).compile().as_text())
        assert any("/wf.unpack/shift_left" in n for n in names), names
        jax.config.update("jax_include_full_tracebacks_in_locations", False)
        fn, args = programs.FAMILIES["unpack"]()
        assert not any("wf.unpack" in n for n in op_names(
            fn.lower(*args).compile().as_text()))
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          keep[0])
        jax.config.update("jax_traceback_in_locations_limit", keep[1])


def test_a_pallas_program_lowers_the_same_from_any_call_stack(monkeypatch,
                                                              tmp_path):
    """What ``setup_compile_cache`` set the location flag for in the
    first place: a kernel travels inside the HLO with its locations, so
    they may not depend on who built the graph."""
    from windflow_tpu import kernels as pk
    from windflow_tpu.compile_cache import setup_compile_cache
    from windflow_tpu.windows import ffat_kernels as fk
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keep = (jax.config.jax_include_full_tracebacks_in_locations,
            jax.config.jax_traceback_in_locations_limit)
    S = jax.ShapeDtypeStruct

    def lower():
        step = fk.make_ffat_step(4096, 64, 4, 4, 2, lambda x: x["v"],
                                 programs.ADD, lambda x: x["k"],
                                 monoid="sum", pallas=pk.PallasMode(False))
        state = jax.eval_shape(lambda: fk.make_ffat_state(
            jnp.zeros((), jnp.float32), 64, 4))
        return jax.export.export(jax.jit(step), platforms=["tpu"])(
            state, {"k": S((4096,), jnp.int32), "v": S((4096,), jnp.float32)},
            S((4096,), jnp.int64), S((4096,), jnp.bool_)) \
            .mlir_module_serialized

    def deeper(n):
        return lower() if n == 0 else deeper(n - 1)

    try:
        setup_compile_cache()
        assert lower() == deeper(3)
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          keep[0])
        jax.config.update("jax_traceback_in_locations_limit", keep[1])


def test_phase_refuses_a_name_that_is_not_declared():
    with pytest.raises(ValueError, match="not a device phase"):
        recorder.phase("wf.nonesuch")


def test_phases_do_not_nest_and_an_operator_is_named_once():
    def nested(x):
        with recorder.phase("wf.place"):
            with recorder.phase("wf.fire"):
                return x + 1

    def twice(x):
        with recorder.operator_scope("a"), recorder.operator_scope("b"):
            return x + 1

    def operator_inside_phase(x):
        with recorder.phase("wf.place"), recorder.operator_scope("a"):
            return x + 1

    for bad in (nested, twice, operator_inside_phase):
        with pytest.raises(ValueError, match="opened inside"):
            jax.make_jaxpr(bad)(1.0)
    # what a refused trace leaves behind does not poison the next one
    def good(x):
        with recorder.operator_scope("a|b c"), recorder.phase("wf.fn"):
            return x + 1
    text = jax.jit(good).lower(1.0).compile().as_text()
    assert any(n.endswith("wf.op.a_b_c/wf.fn/add") for n in op_names(text))


def test_the_sketch_and_the_egress_pack_carry_their_phase():
    from windflow_tpu.monitoring.shard_ledger import (ShardSketch,
                                                      device_sketch_init)
    from windflow_tpu.ops.chained import fuse
    ma = (wf.MapTPU_Builder(lambda t: {"k": t["k"], "v": t["v"] * 2.0})
          .withName("ma").build())
    fb = (wf.FilterTPU_Builder(lambda t: (t["k"] & 1) == 0)
          .withName("fb").build())
    chain = fuse(ma, fb)._chain
    chain.set_downstream_key_extractor(lambda t: t["k"])
    chain.attach_shard_sketch(ShardSketch(4), 4)
    payload, _, valid = programs._batch()
    names = op_names(chain._jit._jit.lower(
        payload, valid, device_sketch_init(4)).compile().as_text())
    assert any("/wf.shard.sketch/dot_general" in n for n in names)
    assert {o for n in names for o in OPERATOR.findall(n)} == {"ma", "fb"}
    # the sketch belongs to no operator of the chain
    assert not any("wf.op." in n for n in names if "wf.shard.sketch" in n)

    b = wbatch.DeviceBatch({"key": jnp.arange(8, dtype=jnp.int32),
                            "value": jnp.ones(8, jnp.float32)},
                           jnp.arange(8, dtype=jnp.int64),
                           jnp.ones(8, bool))
    ok, leaves, treedef, cap = wbatch._egress_packable(b)
    assert ok
    wbatch._egress_pack(b, leaves, treedef, cap)
    # ... and so does the program that packs a batch's front only
    wbatch._egress_pack(b, leaves, treedef, cap, front=4)
    whole, front = [[v for k, v in wbatch._EGRESS_PACK_CACHE.items()
                     if k[2] == 8 and len(k[1]) == 2 and k[3:] == rest]
                    for rest in ((None,), (4,))]
    for (pack,) in (whole, front):
        names = op_names(pack._jit.lower(leaves, b.ts, b.valid).compile()
                         .as_text())
        assert any("/wf.egress.pack/" in n for n in names)
        assert not any("wf.op." in n for n in names)


def _sources():
    for top, _, files in os.walk(os.path.join(ROOT, "windflow_tpu")):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(top, f)
                with open(path) as fh:
                    yield os.path.relpath(path, ROOT), fh.read()


def test_every_phase_is_opened_somewhere_and_documented():
    sources = dict(_sources())
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    section = doc[doc.index("## Device phases"):]
    section = section[:section.index("\n## ", 10)]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        import json
        layers = {m["layer"] for m in json.load(f)["per_layer"]}
    for name, (layer, covers) in recorder.PHASES.items():
        assert any(f'phase("{name}")' in text for text in sources.values()), \
            f"{name} is declared and opened nowhere"
        assert f"`{name}`" in section, f"{name} is not in the docs' table"
        assert layer in layers, (name, layer)
        assert covers and "\n" not in covers
    # ... and nothing opens a scope but through the two primitives
    assert [p for p, text in sources.items() if "named_scope" in text] \
        == [os.path.join("windflow_tpu", "monitoring", "recorder.py")]
    opened = {m for text in sources.values()
              for m in re.findall(r'phase\("(wf\.[a-z_.]+)"\)', text)}
    assert opened == set(recorder.PHASES)
