"""Device time by program scope and idle time by program span
(bench/scopes.py), and the program's row counters."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, tracing
from bench.scopes import ModuleEv
from bench.tracing import Ev


@pytest.mark.parametrize("op,scope", [
    ("jit(run)/jit(build_network_plan)/plan/search/jit(zdelta_search)/"
     "jit(searchsorted)/vmap(vmap())/while", "plan/search"),
    ("jit(run)/jit(build_network_plan)/plan/sort/jit(sort)/sort",
     "plan/sort"),
    ("jit(run)/jit(build_network_plan)/jit(run)/jit(build_network_plan)/"
     "plan/downsample/jit(searchsorted)/plan/downsample/vmap()/while/body/"
     "closed_call/gather", "plan/downsample"),
    ("jit(run)/s1_r0a/conv/jit(output_stationary)/jit(spconv_gather_gemm)/"
     "spconv_gather_gemm/pallas_call", "s1_r0a/conv"),
    ("jit(run)/s1_r0a/norm/jit(_where)/select_n", "s1_r0a/norm"),
    ("transpose(jvp(jit(step)))/enc1_b/norm/mul", "enc1_b/norm"),
    ("jit(run)/head/dot_general", "head"),
    ("jit(run)/outputs/reduce_sum", "outputs"),
    ("jit(run)/plan/segments/searchsorted", "plan/segments"),
    ("jit(searchsorted)/vmap(vmap())/while/body/closed_call/gather", None),
    ("jit(run)/jit(build_network_plan)", None),
    ("jit(run)/jit(sort)/sort", None),
    ("", None),
])
def test_scope_of(op, scope):
    assert scopes.scope_of(op) == scope


HLO = """HloModule jit_run, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%body (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p = (s32[], s32[8]{0}) parameter(0)
  %gather.1 = s32[8]{0} gather(s32[8]{0} %gte.1), metadata={op_name="jit(searchsorted)/while/body/gather"}
  ROOT %tuple.1 = (s32[], s32[8]{0}) tuple(s32[] %gte.0, s32[8]{0} %gather.1)
}

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(s32[8]{0} %param_0, s32[8]{0} %param_0)
}

ENTRY %main.9 (packed.1: s32[8]) -> s32[8] {
  %packed.1 = s32[8]{0} parameter(0)
  %constant.1 = s32[] constant(2147483647), metadata={op_name="jit(run)"}
  %broadcast.1 = s32[8]{0} broadcast(s32[] %constant.1), dimensions={}, metadata={op_name="jit(run)/jit(build_network_plan)"}
  %sort.1 = s32[8]{0} sort(s32[8]{0} %packed.1, s32[8]{0} %broadcast.1), metadata={op_name="jit(run)/plan/sort/sort"}
  %copy.1 = s32[8]{0} copy(s32[8]{0} %sort.1)
  %while.1 = (s32[], s32[8]{0}) while((s32[], s32[8]{0}) %tuple.0), condition=%cond, body=%body, metadata={op_name="jit(run)/plan/search/while"}
  ROOT %fusion.1 = s32[8]{0} fusion(s32[8]{0} %copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(run)/l0/norm/add"}
}
"""


def test_hlo_scopes_inherit_by_caller_operand_and_user():
    sc = scopes.hlo_scopes(HLO)
    assert scopes.hlo_module(HLO) == "jit_run"
    assert sc["sort.1"] == "plan/sort"              # its own op name
    assert sc["gather.1"] == "plan/search"          # the while calls its body
    assert sc["add.1"] == "l0/norm"                 # the fusion calls it
    assert sc["copy.1"] == "plan/sort"              # from its operand
    assert sc["broadcast.1"] == "plan/sort"         # from its user


def test_scope_time_by_instruction_of_each_module():
    """The TPU's events carry no op name: each op's self time goes to the
    scope of its instruction in the compiled program of its module (a
    while loop and the body ops nested in it alike), and the same
    instruction name in another module is not taken for it."""
    evs = [ModuleEv("%while.1 = (s32[], s32[8]{0}) while(%tuple.0)", 0, 100,
                    module="jit_run"),
           ModuleEv("%gather.1 = s32[8]{0} gather(%gte.1)", 10, 30,
                    module="jit_run"),
           ModuleEv("%sort.1 = s32[8]{0} sort(s32[8]{0} %packed.1)", 100,
                    110, module="jit_run"),
           ModuleEv("%copy.1 = s32[8]{0} copy(s32[8]{0} %sort.1)", 110, 115,
                    module="jit_run"),
           ModuleEv("%fusion.1 = s32[8]{0} fusion(%copy.1)", 115, 135,
                    module="jit_run"),
           ModuleEv("%copy.1 = s32[8]{0} copy(s32[8]{0} %x)", 140, 142,
                    module="jit_squeeze"),
           ModuleEv("%fusion.7 = s32[8]{0} fusion(%copy.1)", 142, 145)]
    ops = {"/device:TPU:0": evs}
    got = scopes.scope_times(ops, (0, 150),
                             {"jit_run": scopes.hlo_scopes(HLO)})
    assert got == pytest.approx({"plan/search": 100e-9, "plan/sort": 15e-9,
                                 "l0/norm": 20e-9, "unscoped": 5e-9})
    busy = tracing.summarize(ops, [], (0, 150)).busy_s
    assert sum(got.values()) == pytest.approx(busy)


def test_ops_are_tagged_with_the_module_run_that_holds_them():
    evs = [Ev("a.1", 5, 6), Ev("b.1", 12, 13), Ev("c.1", 30, 31)]
    runs = [(10, 20, "jit_run"), (0, 8, "jit_squeeze")]
    got = scopes.in_modules(evs, runs)
    assert [e.module for e in got] == ["jit_squeeze", "jit_run", ""]
    assert [e.kernel for e in got] == ["a", "b", "c"]


PROGRAM_SPANS = [Ev("serve/pack", 5, 15), Ev("serve/dispatch", 15, 58),
                 Ev("serve/dispatch/session/call", 16, 57),
                 Ev("serve/answer", 58, 60),
                 Ev("serve/pack", 60, 65), Ev("serve/dispatch", 65, 98),
                 Ev("serve/dispatch/session/call", 66, 97),
                 Ev("serve/answer", 98, 100)]


def test_program_spans_name_the_gaps_and_leave_device_readings():
    """The program's spans cut and name the idle gaps; busy and kernel
    time stay as the benchmark's spans alone give them."""
    bench = [Ev("bench/window", 0, 100), Ev("bench/request", 10, 60),
             Ev("bench/request", 60, 100)]
    ops = {"/device:TPU:0": [Ev("k.1", 20, 30), Ev("k.2", 40, 50),
                             Ev("spconv_gather_gemm.3", 70, 95)]}
    a = tracing.summarize(ops, bench, (0, 100))
    b = tracing.summarize(ops, bench + PROGRAM_SPANS, (0, 100))
    for k in ("window_s", "busy_s", "idle_share", "kernel_s", "pallas_s",
              "xla_s", "device_ops"):
        assert getattr(a, k) == getattr(b, k), k
    gaps = dict(b.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(a.window_s - a.busy_s)
    assert gaps["serve/dispatch/session/call (between device ops)"] == \
        pytest.approx(10e-9)                               # 30-40
    assert gaps["serve/dispatch/session/call (before device work)"] == \
        pytest.approx(4e-9 + 4e-9)                         # 16-20, 66-70
    assert gaps["serve/pack (before device work)"] == pytest.approx(
        10e-9 + 5e-9)                                      # 5-15, 60-65
    assert gaps["serve/answer (before device work)"] == pytest.approx(4e-9)


def test_program_spans_are_read_from_the_trace(tmp_path):
    from repro.obs import MetricsRegistry, span

    @jax.jit
    def f(x):
        return jnp.sin(x) @ x

    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/window"):
        with span("serve/dispatch", reg, batch=4):
            with span("session/call", reg):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    prof = tracing.load(str(tmp_path))
    assert [s.name for s in tracing.host_spans(prof)] == ["bench/window"]
    names = {s.name for s in scopes.program_spans(prof)}
    assert names == {"serve/dispatch", "serve/dispatch/session/call"}
    ops = scopes.module_ops(prof, on_chip=False)
    assert any(e.module.startswith("jit_f") for e in ops["/host:CPU"])


def _program_session(config: str):
    """The program's serving session for a configuration file, as the serve
    mode compiles it, with the reference's description of the network."""
    from bench import traffic
    from bench.modes import common
    from bench.tests import tiny
    from repro.core.packing import BitLayout
    from repro.serve import compile_network
    cell = tiny.config_cell(config)
    tr = dict(tiny.SERVE["traffic"], pool=2)
    layout = BitLayout.for_extent(*tr["extent"], guard=traffic.GUARD)
    session = compile_network(common.checked_program_net(cell), layout)
    return cell, tr, session


def test_every_op_of_the_serving_program_has_a_scope():
    """Every fusion, custom call and while loop of the compiled serving
    program of tiny_segnet names a program scope, or takes one from the
    instruction that calls it or from its data (hlo_scopes): the device
    time of that program is all charged to some scope."""
    cell, _, session = _program_session("bench/tests/tiny_segnet.json")
    cap, cin = 4096, cell.net.in_channels
    text = session._make_fn(0).lower(
        session.params, jax.ShapeDtypeStruct((cap,), jnp.int32),
        jax.ShapeDtypeStruct((cap, cin), jnp.float32)).compile().as_text()
    sc = scopes.hlo_scopes(text)
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*?\s(fusion|custom-call|while)"
                     r"\(", text, re.M)
    assert len(ops) > 10
    missing = [name for name, _ in ops if name not in sc]
    assert not missing, missing
    named = dict(re.findall(r'^\s*(?:ROOT )?%(\S+) = [^\n]*?op_name="'
                            r'([^"]*)"', text, re.M))
    own = [n for n, _ in ops if scopes.scope_of(named.get(n, ""))]
    assert len(own) >= 0.5 * len(ops)     # most name their scope themselves
    layers = {L.name for L in cell.net.layers}
    assert {f"{n}/conv" for n in layers} | {f"{n}/norm" for n in layers} \
        | {"plan/sort", "plan/search", "head"} <= set(sc.values())


@pytest.mark.parametrize("config", ["bench/tests/tiny_segnet.json",
                                    "bench/configs/sparse_resnet21.json"])
def test_rows_real_are_the_reference_voxel_counts(config):
    """The session's row counters: every OS conv walks its map's rows (the
    bucket), and the real rows are the reference's voxel count of the
    conv's output level."""
    from bench import reference, traffic
    from repro.core import SparseTensor
    cell, tr, session = _program_session(config)
    reg = session.metrics
    for (scan,) in traffic.scan_pool(2 ** 31 + 11, tr):
        feats = traffic.scan_features(scan, cell.net.in_channels)
        walked0 = reg.counter("spconv_rows_walked").value
        real0 = reg.counter("spconv_rows_real").value
        session(SparseTensor.from_point_clouds([(scan.coords, feats)],
                                               session.layout))
        hs = reference.build_scan(scan.coords, cell.net)
        bucket = session.last_health.bucket
        assert reg.counter("spconv_rows_walked").value - walked0 == \
            len(cell.net.layers) * bucket
        assert reg.counter("spconv_rows_real").value - real0 == sum(
            hs.count(L.m_out) for L in cell.net.layers)


def test_every_reader_on_a_fixed_trace():
    """Each per-layer metric of the serving cell read from one fixed trace
    and fixed spans: program spans beside the benchmark's change none of
    the readings."""
    from bench import harness
    from bench.tests import tiny
    evs = [
        Ev("%sort.1 = s32[8] sort()", 0, 50),
        Ev("%fusion.1 = s32[8] fusion()", 50, 80),
        Ev("%while.1 = s32[8] while()", 80, 280),
        Ev("%fusion.2 = s32[8] fusion()", 100, 200),
        Ev("%spconv_gather_gemm.1 = f32[8,16] custom-call(), "
           'custom_call_target="tpu_custom_call"', 300, 700),
        Ev("%segment_sum_pallas.1 = f32[8,32] custom-call(), "
           'custom_call_target="tpu_custom_call"', 700, 720),
        Ev("%fusion.3 = f32[8,16] fusion()", 720, 760),
        Ev("%fusion.4 = f32[8,5] fusion()", 760, 770),
        Ev("%copy.1 = s32[8] copy()", 800, 810),
    ]
    want = {
        "mfu.serve": 0.1,                       # 1e3 / 1e-6 s over 1e12
        "device_idle_share.serve": 24.0,        # 760 of 1000 ns busy
        "os_gemm_roofline.serve": 10.0,         # 4 kB / 1e11 over 400 ns
        "segsum_ms_per_scan.serve": 1e-5,       # 20 ns / 2 scans
        "xla_ops_ms_per_scan.serve": 1.7e-4,    # 340 ns / 2
        "host_pack_ms.serve": 50.0,
    }
    names = [m["name"] for m in tiny.benchmark()["per_layer"]]
    assert sorted(names) == sorted(want)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    for spans in ([Ev("bench/window", 0, 1000)],
                  [Ev("bench/window", 0, 1000)] + PROGRAM_SPANS):
        summary = tracing.summarize({"/device:TPU:0": evs}, spans, (0, 1000))
        ctx = {"mode": "serve", "trace": summary, "units": 2,
               "work": {"forward_flops": 1e3, "os_flops": 8.0,
                        "os_bytes": 4e3},
               "spans": {"serve/pack": (2, 0.1)}, "peaks": peaks}
        for name in names:
            reader = harness.load_module(
                tiny.BENCH / "metrics" / f"{name}.py", f"t_read_{name}")
            assert reader.read(ctx)["value"] == pytest.approx(want[name]), \
                name


def test_report_on_the_cpu(tmp_path, capsys):
    """The command serves a tiny cell under a trace and reads it by scope:
    on the CPU every op of the serving program lands in some scope, the
    idle gaps are named by program spans, and the row counters give the
    padding share."""
    from bench.tests import tiny
    root = tiny.make_root(tmp_path, {"added.rooms": tiny.SERVE})
    rc = scopes.main(["--workload", "added.rooms", "--seed", str(2 ** 31 + 5),
                      "--seconds", "0.5"], root=root, need_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0, err
    got = json.loads(out.strip().splitlines()[-1])
    assert got["scans"] >= 1 and got["failed"] == 0
    by = got["ms_per_scan_by_stage"]
    assert {"conv", "norm", "plan/search", "plan/sort", "head"} <= set(by)
    # the engine's eager pack and unpack programs are the unscoped rest
    assert by.get("unscoped", 0.0) < 0.01 * sum(by.values())
    assert got["unscoped_share_of_busy"] < 1.0
    assert 0.0 < got["os_padding_row_share"] < 100.0
    assert got["span_mean_ms"]["serve/answer"] > 0
    gaps = {name.split(" (")[0] for name, _ in got["idle_gaps"]}
    assert gaps & {"serve/pack", "serve/answer",
                   "serve/dispatch/session/call"}, gaps
