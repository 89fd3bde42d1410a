"""Scopes, idle time by engine phase and gap labels from trace events."""
import gzip
import json
import pathlib

import pytest

import phases
import tracefile
from test_tracefile import DEV, PROGRAMS, host, module, op

SITES = {"attn.q", "attn.kv", "mlp.up", "lm_head"}
SLICE_SITES = {"attn.q", "attn.k", "attn.v", "attn.out", "attn.kv",
               "attn.score", "attn.value", "mlp.up", "mlp.gate",
               "mlp.down", "lm_head"}
SCOPED_SLICE = pathlib.Path(__file__).resolve().parent / "data" / \
    "trace_slice_scopes.json.gz"


def scoped(name, start, dur, scope, kernel=False):
    e = op(name, start, dur, kernel=kernel)
    e["scope"] = scope
    return e


def engine(name, start, dur):
    return host(name, start, dur)


def hand_made():
    return [
        host("chipbench.window", 0, 10000),
        host("chipbench.step", 0, 6000),
        engine("engine.step", 100, 5800),
        engine("engine.admit", 100, 150),
        engine("engine.prefill", 250, 1750),
        engine("engine.push_table", 2000, 600),
        engine("engine.decode", 2600, 400),
        engine("engine.sync", 3000, 2500),
        engine("engine.retire", 5500, 400),
        host("chipbench.wait", 6000, 4000),
        # prefill program 500..1900: plan, kernel, an unscoped add
        module("jit__prefill_impl(7)", 500, 1400),
        scoped("sort.1", 500, 400, "jit(_prefill_impl)/mlp.up/plan/sort"),
        scoped("bitmap_spgemm_kfused_planned.2", 900, 600,
               "jit(_prefill_impl)/mlp.up/kernel/"
               "jit(bitmap_spgemm_kfused_planned)/pallas_call",
               kernel=True),
        scoped("add.3", 1500, 400, "jit(_prefill_impl)/add"),
        # decode program 3100..5400; its last 200 ns hold no op
        module("jit__decode_impl(3)", 3100, 2300),
        scoped("gather.4", 3100, 500,
               "jit(_decode_impl)/while/body/closed_call/attn.kv/gather"),
        scoped("fusion.5", 3600, 800, "jit(_decode_impl)/lm_head/plan/and"),
        scoped("dot.6", 4400, 600, "jit(_decode_impl)/lm_head/xla/dot"),
        scoped("copy.7", 5000, 200,
               "jit(_decode_impl)/while/body/closed_call/attn.q/reshape"),
    ]


def test_site_part():
    assert phases.site_part(
        "jit(f)/while/body/closed_call/mlp.up/kernel/"
        "jit(bitmap_spgemm_kfused_planned)/pallas_call", SITES) == \
        ("mlp.up", "kernel")
    assert phases.site_part("jit(f)/lm_head/dot_general", SITES) == \
        ("lm_head", "other")
    assert phases.site_part("jit(f)/attn.kv", SITES) == ("attn.kv",
                                                        "other")
    assert phases.site_part("jit(f)/add", SITES) == (None, "")
    assert phases.site_part("", SITES) == (None, "")


def test_hand_made_scopes_and_phases():
    r = phases.reduce(hand_made(), PROGRAMS, SITES)
    pre, dec = r["scopes"]["prefill"], r["scopes"]["decode"]
    assert pre["device_s"] == pytest.approx(1400e-9)
    assert pre["sites"] == {"mlp.up/plan": pytest.approx(400e-9),
                            "mlp.up/kernel": pytest.approx(600e-9)}
    assert pre["unscoped_s"] == pytest.approx(400e-9)
    assert pre["unscoped_ops"] == {
        "add.3 [jit(_prefill_impl)/add]": pytest.approx(400e-9)}
    assert dec["sites"] == {"attn.kv/other": pytest.approx(500e-9),
                            "lm_head/plan": pytest.approx(800e-9),
                            "lm_head/xla": pytest.approx(600e-9),
                            "attn.q/other": pytest.approx(200e-9)}
    assert dec["unscoped_s"] == pytest.approx(200e-9)   # no op there
    assert dec["unscoped_ops"] == {}
    assert r["scopes"]["insert"]["device_s"] == 0.0
    assert phases.plan_s(r["scopes"], "decode") == pytest.approx(800e-9)
    # idle: 0..500, 1900..3100 and 5200..10000, by what the host was in
    want = {"step": 200, "step/engine.admit": 150,
            "step/engine.prefill": 350, "step/engine.push_table": 600,
            "step/engine.decode": 400, "step/engine.sync": 400,
            "step/engine.retire": 400, "wait": 4000}
    assert r["idle_by_phase"] == {k: pytest.approx(v * 1e-9)
                                  for k, v in want.items()}
    assert phases.step_idle_s(r["idle_by_phase"]) == pytest.approx(
        (150 + 350 + 600 + 400 + 400 + 400) * 1e-9)
    assert r["idle_gaps"] == [["wait", pytest.approx(4800e-9)],
                              ["step/engine.push_table",
                               pytest.approx(1200e-9)],
                              ["step/engine.prefill",
                               pytest.approx(500e-9)]]


def test_tracefile_reads_the_extended_events_the_same():
    """tracefile's reduction of the extended events equals its reduction
    of the events it extracts itself (no scopes, no engine spans)."""
    events = hand_made()
    plain = [{k: v for k, v in e.items() if k != "scope"}
             for e in events if not e["name"].startswith("engine.")]
    assert tracefile.reduce(events, PROGRAMS) == \
        tracefile.reduce(plain, PROGRAMS)
    gaps = [g[1] for g in tracefile.reduce(events, PROGRAMS)["idle_gaps"]]
    assert gaps == [g[1] for g in phases.reduce(events, PROGRAMS,
                                                SITES)["idle_gaps"]]


def test_no_host_span_reads_none():
    events = [host("chipbench.window", 0, 1000),
              module("jit__decode_impl(1)", 0, 400),
              scoped("fusion.1", 0, 400, "jit(_decode_impl)/mlp.up/plan/x")]
    r = phases.reduce(events, PROGRAMS, SITES)
    assert r["idle_by_phase"] == {"none": pytest.approx(600e-9)}
    assert r["idle_gaps"] == [["none", pytest.approx(600e-9)]]
    assert r["scopes"]["decode"]["sites"] == {
        "mlp.up/plan": pytest.approx(400e-9)}
    assert DEV == events[1]["plane"]


def _pb(*fields) -> bytes:
    """A protobuf message's wire form: (number, int | bytes | str)."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def _instruction(name, iid, op_name=None, operands=(), calls=()):
    fields = [(1, name), (2, "x"), (35, iid)]
    if op_name is not None:
        fields.append((7, _pb((1, "x"), (2, op_name))))
    fields += [(36, o) for o in operands] + [(38, c) for c in calls]
    return _pb(*fields)


def _hlo_proto(entry_kernel="mlp.up/kernel"):
    """A compiled module: a fused computation whose ops name a plan
    scope, and an entry computation whose compiler-made ops carry no
    metadata, or an ``op_name`` outside the program's ``jit(...)``."""
    fused = _pb((1, "fused_computation"), (5, 2),
                (2, _instruction("param_0", 1)),
                (2, _instruction("and.1", 2, "jit(f)/mlp.up/plan/and",
                                 operands=[1])))
    entry = _pb((1, "main"), (5, 1),
                (2, _instruction("x.1", 1, "x")),
                (2, _instruction("copy.8", 2, operands=[1])),
                (2, _instruction("fusion.2", 3, operands=[2], calls=[2])),
                (2, _instruction("sort.5", 4, operands=[3])),
                (2, _instruction("fusion.6", 5, "reduce_window_sum",
                                 operands=[4])),
                (2, _instruction("probe_kernel.3", 6,
                                 f"jit(f)/{entry_kernel}/pallas_call",
                                 operands=[5])),
                (2, _instruction("copy.9", 7, operands=[6])),
                (2, _instruction("add.4", 8, "jit(f)/add")))
    return _pb((1, _pb((1, "jit_f"), (3, fused), (3, entry))))


def test_module_scopes_from_hlo_proto():
    """Scopes below the program's jit(...); an op without one takes the
    scope of the ops it calls, else of its nearest named neighbour along
    the data flow, producers first."""
    got = phases.module_scopes(_hlo_proto())
    assert got["and.1"] == "mlp.up/plan/and"
    assert got["fusion.2"] == "mlp.up/plan/and"         # what it calls
    assert got["copy.8"] == "mlp.up/plan/and"           # its user
    assert got["x.1"] == "mlp.up/plan/and"              # two steps away
    # a chain that lost its metadata: each op to its nearest named one,
    # producers first at equal distance
    assert got["sort.5"] == "mlp.up/plan/and"
    assert got["fusion.6"] == "mlp.up/kernel/pallas_call"
    assert got["probe_kernel.3"] == "mlp.up/kernel/pallas_call"
    assert got["copy.9"] == "mlp.up/kernel/pallas_call"  # its operand
    assert got["add.4"] == "add"


def test_hlo_scopes_of_a_profile(tmp_path):
    """A CPU profile stores each compiled module: its ops' scopes come
    back under the module's name as its events give it."""
    import glob
    import os

    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp.up"), jax.named_scope("plan"):
            idx = jnp.argsort(x)
        with jax.named_scope("lm_head"), jax.named_scope("xla"):
            return jnp.tanh(x[idx] * 3.0)
    g = jax.jit(f)
    x = jnp.arange(64.0)
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        g(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    tables = phases.hlo_scopes(path)
    name, = [k for k in tables if k.startswith("jit_f(")]
    scopes = set(tables[name].values())
    assert any(v.startswith("mlp.up/plan/") for v in scopes), scopes
    assert any(v.startswith("lm_head/xla/") for v in scopes), scopes


def test_each_module_named_by_its_own_program():
    """Two programs reuse an instruction name for different scopes: each
    op takes the scope of the module that ran it."""
    tables = {"jit__prefill_impl(1)": phases.module_scopes(_hlo_proto()),
              "jit__prefill_impl(2)": phases.module_scopes(
                  _hlo_proto("mlp.down/kernel"))}
    events = [module("jit__prefill_impl(1)", 0, 100),
              module("jit__prefill_impl(2)", 100, 100),
              module("jit__insert_impl(3)", 200, 100)]
    ops = [scoped(name, start, 10, None) for start in (0, 100, 200)
           for name in ("fusion.2", "probe_kernel.3")]
    phases.name_ops(events + ops, tables)
    assert [e["scope"] for e in ops] == [
        "mlp.up/plan/and", "mlp.up/kernel/pallas_call",
        "mlp.up/plan/and", "mlp.down/kernel/pallas_call", "", ""]


def test_nested_ops_count_once():
    """A ``while`` op spans the ops of its body: only those count."""
    events = [host("chipbench.window", 0, 1000),
              module("jit__decode_impl(1)", 0, 900),
              scoped("while", 0, 800, "jit(_decode_impl)/while"),
              scoped("sort.1", 0, 300, "jit(_decode_impl)/while/body/"
                     "mlp.up/plan/sort"),
              scoped("fusion.2", 300, 400, "jit(_decode_impl)/while/body/"
                     "mlp.up/xla/dot"),
              scoped("fusion.3", 800, 100, "jit(_decode_impl)/lm_head/"
                     "plan/and")]
    r = phases.reduce(events, PROGRAMS, SITES)
    assert r["scopes"]["decode"]["sites"] == {
        "mlp.up/plan": pytest.approx(300e-9),
        "mlp.up/xla": pytest.approx(400e-9),
        "lm_head/plan": pytest.approx(100e-9)}
    assert r["scopes"]["decode"]["unscoped_s"] == pytest.approx(100e-9)


def test_recorded_slice_with_scopes():
    """A slice of a TPU v5e trace of nemotron-4-340b.chat recorded by
    ``phases.py --slice`` (two prefills with their inserts, two decode
    steps): every idle nanosecond gets a label, the longest gaps an
    engine phase, and the scoped and unscoped time add up to each
    program's device time.  Its scopes were named before they were
    carried along the data flow (:func:`phases.module_scopes`), so about
    half of the decode time is unscoped in it."""
    with gzip.open(SCOPED_SLICE, "rt") as f:
        events = json.load(f)
    base = tracefile.reduce(events, PROGRAMS)
    r = phases.reduce(events, PROGRAMS, SLICE_SITES)
    assert [base["programs"][k]["calls"] for k in PROGRAMS] == [2, 2, 2]
    assert sum(r["idle_by_phase"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])
    assert [g[1] for g in r["idle_gaps"]] == [g[1] for g in
                                              base["idle_gaps"]]
    assert all(label.split("/")[-1].startswith("engine.")
               for label, _ in r["idle_gaps"][:3])
    for role in ("prefill", "decode"):
        sc = r["scopes"][role]
        assert sc["device_s"] == pytest.approx(
            base["programs"][role]["device_s"])
        assert sum(sc["sites"].values()) + sc["unscoped_s"] == \
            pytest.approx(sc["device_s"])
    for part in ("plan", "kernel"):
        assert any(k.endswith("/" + part)
                   for k in r["scopes"]["decode"]["sites"])


def test_scopes_reach_what_the_tpu_compiler_makes():
    """The v5e compiler turns the planner's scatter and cumsums into
    fusions, copies and windowed sums without the scope's ``op_name``:
    every instruction of the compiled module still gets the scope."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.sparse import plan as pln
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e topology can be described here: {e}")

    def f(act):
        with jax.named_scope("mlp.down"), jax.named_scope("plan"):
            return pln.stable_partition(act)
    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct(
        (32, 73728), jnp.bool_,
        sharding=SingleDeviceSharding(topo.devices[0]))).compile()
    module, = compiled.runtime_executable().hlo_modules()
    got = phases.module_scopes(
        _pb((1, module.as_serialized_hlo_module_proto())))
    entry = compiled.as_text().split("\nENTRY ", 1)[1].splitlines()[1:]
    own = {line.split(" = ")[0].split("%")[1]:
           'op_name="jit(f)/mlp.down/plan/' in line
           for line in entry if " = " in line}
    assert not all(own.values())  # the compiler made some without one
    assert {name: got[name].startswith("mlp.down/plan/")
            for name in own} == dict.fromkeys(own, True)
