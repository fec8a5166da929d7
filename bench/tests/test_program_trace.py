"""The program's own names in a trace: kernel time by family, the idle
split by program span, and the per-layer metrics that read them, on
hand-made events and on a trace recorded on a TPU v5e."""
import os
import shutil
import tempfile

import pytest

from bench import harness, program_trace as P, reduce_trace as R, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN = os.path.join(DATA, "train_v5e.xplane.pb")
DECODE = os.path.join(DATA, "decode_v5e.xplane.pb")
YI = harness.config("yi-6b")
LM = harness.config("h1d-lm-144m")
PEAKS = harness.peaks("TPU v5 lite")


def kernel(name, family=None, escaped=True):
    """A Mosaic kernel event's name, as the trace prints it."""
    if family is None:
        meta = "{}"
    elif escaped:
        meta = '"{\\n\\"family\\":\\"%s\\",\\n\\"kernel\\":\\"k\\"\\n}"' % family
    else:
        meta = '{\n"family":"%s",\n"kernel":"k"\n}' % family
    return (f"%{name} = f32[8,128] custom-call(f32[8,128] %p), "
            f'custom_call_target="tpu_custom_call", '
            f"frontend_attributes={{kernel_metadata={meta}}}")


def test_kernel_seconds_by_family():
    ops = {kernel("band_fwd.1", "band_fwd"): 2.0,
           kernel("band_fwd.2", "band_fwd", escaped=False): 1.0,
           kernel("closed_call.7", "decode_attend_paged"): 0.5,
           kernel("closed_call.8"): 0.25,
           "%fusion.3 = f32[8] fusion(%a)": 4.0,
           "%while.1 = (s32[]) while(%t)": 8.0}
    assert P.kernel_seconds(ops) == {"band_fwd": 3.0,
                                     "decode_attend_paged": 0.5,
                                     "unnamed": 0.25}
    assert P.kernel_seconds({"%fusion.3 = f32[8] fusion(%a)": 1.0}) == {}


def test_idle_split_by_overlap_among_innermost_program_spans():
    spans = ["serve.tick", "serve.tables", "serve.decode"]
    host = [("serve.step", 0, 100),              # the harness's, not ours
            ("serve.tick", 5, 95),
            ("serve.tables", 10, 30),
            ("serve.decode", 30, 60),
            ("Linearize", 35, 55)]               # the runtime's, inside
    # one gap over tables and decode, one inside decode over Linearize,
    # one across the tick's end into no program span
    gaps = [(20, 40), (50, 58), (90, 100)]
    got = P.idle_by_program_span(gaps, host, spans)
    assert got == pytest.approx({"serve.tables": 10e-9,
                                 "serve.decode": 18e-9,
                                 "serve.tick": 5e-9,
                                 P.NO_SPAN: 5e-9})
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in gaps) * 1e-9)


def test_window_is_the_reductions():
    host = [("serve.step", 0, 100), ("serve.tick", 0, 100),
            ("serve.decode", 10, 20)]
    dev = {0: {"XLA Ops": [("a", 15, 40), ("b", 30, 50), ("c", 70, 130)]},
           1: {"XLA Ops": [("a", 0, 100)]}}
    for devs in (1, 2):
        red = R.reduce_events(dev, host, devs=devs)
        w = P.window(dev, host, devs=devs)
        assert (w["window_s"], w["busy_s"]) == (red["window_s"],
                                                red["busy_s"])
        assert w["gaps"] == [(0, 15), (50, 70)]        # the first chip's


def decode_events(ticks=3):
    """Engine ticks of 100 ns: admit, prepare, tables, decode (the device
    runs from 50 to 85), sample, readback (waits for the device),
    bookkeep; the runtime's events nest inside the engine's spans."""
    host, ops = [], []
    for i in range(ticks):
        t = 100 * i
        host += [("serve.step", t, t + 100), ("serve.tick", t + 1, t + 99),
                 ("serve.admit", t + 2, t + 10),
                 ("serve.prepare", t + 10, t + 20),
                 ("serve.tables", t + 20, t + 35),
                 ("TransferToDevice", t + 25, t + 33),
                 ("serve.decode", t + 35, t + 55),
                 ("Linearize", t + 36, t + 49),
                 ("serve.sample", t + 55, t + 60),
                 ("serve.readback", t + 60, t + 88),
                 ("serve.bookkeep", t + 88, t + 97)]
        ops += [(kernel(f"attend.{i}", "decode_attend_paged"), t + 50,
                 t + 60),
                (kernel(f"update.{i}", "decode_update_paged"), t + 60,
                 t + 65),
                (f"%fusion.{i} = f32[8] fusion(%a)", t + 65, t + 85)]
    return {0: {"XLA Ops": ops}}, host


def readings(dev, host, positions=None, steps=None, chips=1):
    red = R.reduce_events(dev, host)
    window = {"seconds": red["window_s"]}
    if positions is not None:
        window.update(ticks=len(positions), positions=positions)
    if steps is not None:
        window["steps"] = steps
    return {"trace": red, "work": work, "cfg": YI if steps is None else LM,
            "mix": {"batch": 2, "seq_len": 4096}, "window": window,
            "peaks": PEAKS, "chips": chips}


def as_trace_file(monkeypatch, dev, host):
    """Let ``program`` find the events as the run's one trace file."""
    monkeypatch.setattr(P, "trace_files", lambda: ["run.xplane.pb"])
    monkeypatch.setattr(R, "events", lambda path: (dev, host))


@pytest.fixture
def decode_reading(monkeypatch):
    dev, host = decode_events()
    as_trace_file(monkeypatch, dev, host)
    return readings(dev, host, positions=[[8000] * 8, [8001] * 8,
                                          [8002] * 8])


# idle seconds a tick: admit 8 + prepare 10; tables 15; decode until the
# device starts, 15; readback 3 + bookkeep 9 + the tick's own 1 + 2
IDLE = {"idle_prepare_ms_per_tick.decode": 18e-9,
        "idle_tables_ms_per_tick.decode": 15e-9,
        "idle_dispatch_ms_per_tick.decode": 15e-9,
        "idle_tokens_ms_per_tick.decode": 15e-9}


@pytest.mark.parametrize("name", sorted(IDLE))
def test_idle_readers(decode_reading, name):
    got = harness.metric_reader(name).read(decode_reading)
    assert got == pytest.approx(1000.0 * IDLE[name])


def test_idle_readers_sum_to_the_host_gap(decode_reading):
    parts = [harness.metric_reader(n).read(decode_reading) for n in IDLE]
    gap = harness.metric_reader("host_gap_ms_per_tick.decode").read(
        decode_reading)
    # the window's idle less the 2 ns a tick under the harness's
    # serve.step alone: all of it inside an engine span
    assert sum(parts) <= gap
    assert sum(parts) == pytest.approx(gap - 1000.0 * 6e-9 / 3)


def test_decode_kernel_readers(decode_reading):
    r = decode_reading
    ms = harness.metric_reader("h1d_kernel_ms_per_tick.decode").read(r)
    assert ms == pytest.approx(1000.0 * 15e-9)
    least = sum(work.roofline_seconds(
        work.decode_tick(YI, p)["h1d_flops"],
        work.decode_tick(YI, p)["h1d_bytes"], PEAKS)[0]
        for p in r["window"]["positions"])
    share = harness.metric_reader("h1d_roofline.decode").read(r)
    assert share == pytest.approx(100.0 * least / (3 * 15e-9))


def train_events(*extra):
    host = [("train.dispatch", 0, 5), ("train.wait", 5, 200)]
    dev = {0: {"XLA Ops": [
        ("%while.1 = (s32[]) while(%t)", 10, 190),
        (kernel("band_fwd.1", "band_fwd"), 20, 40),
        (kernel("sub_bwd.2", "sub_bwd"), 100, 130), *extra]}}
    return dev, host


def test_train_kernel_readers():
    r = readings(*train_events(), steps=2)
    ms = harness.metric_reader("h1d_kernel_ms_per_step.train").read(r)
    assert ms == pytest.approx(1000.0 * 50e-9 / 2)
    w = work.train_step(LM, 2, 4096)
    least = work.roofline_seconds(w["h1d_flops"], w["h1d_bytes"], PEAKS)[0]
    share = harness.metric_reader("h1d_roofline.train").read(r)
    assert share == pytest.approx(100.0 * 2 * least / 50e-9)


@pytest.mark.parametrize("extra", [
    kernel("decode_update.3", "decode_update"),     # another family
    kernel("closed_call.3"),                        # unnamed
    kernel("h1d_fwd.3", "h1d_fwd")])                # a renamed family
def test_train_kernel_readers_refuse_kernel_time_they_do_not_count(
        extra, capsys):
    """Kernel time in the window outside the counted families leaves the
    kernel metrics out, and says so, rather than reading as a gain."""
    r = readings(*train_events((extra, 150, 160)), steps=2)
    for name in ("h1d_kernel_ms_per_step.train", "h1d_roofline.train"):
        assert harness.metric_reader(name).read(r) is None, name
    err = capsys.readouterr().err
    assert "kernel ms by family" in err and "left out" in err


def test_readers_find_nothing_in_a_program_without_names(monkeypatch,
                                                         capsys):
    """A program whose kernels carry no family and which writes no spans
    of its own: every new metric is left out, none raises, and standard
    error says why."""
    dev, host = decode_events()
    host = [h for h in host if not h[0].startswith("serve.")
            or h[0] == "serve.step"]
    dev[0]["XLA Ops"] = [(kernel(f"closed_call.{i}"), s, e)
                         for i, (_, s, e) in enumerate(dev[0]["XLA Ops"])]
    as_trace_file(monkeypatch, dev, host)
    r = readings(dev, host, positions=[[8000] * 8] * 3)
    for name in list(IDLE) + ["h1d_kernel_ms_per_tick.decode",
                              "h1d_roofline.decode"]:
        assert harness.metric_reader(name).read(r) is None, name
    err = capsys.readouterr().err
    assert "no program span covers" in err
    assert "['unnamed'] ran in the window" in err


def test_program_reads_the_runs_trace_file(tmp_path, monkeypatch, capsys):
    """``program`` takes the trace that ``harness.traced`` wrote, only
    where its reduction is the reader's, and says why where none is."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    red = R.reduce(DECODE)
    assert P.program({"trace": red, "chips": 1}) is None  # none written
    assert "none of the 0 trace files" in capsys.readouterr().err
    d = tempfile.mkdtemp(prefix="bench_trace_")
    os.makedirs(os.path.join(d, "plugins"))
    shutil.copy(DECODE, os.path.join(d, "plugins", "vm.xplane.pb"))
    r = {"trace": red, "chips": 1}
    got = P.program(r)
    assert (got["window_s"], got["busy_s"]) == (red["window_s"],
                                                red["busy_s"])
    assert P.program(r) is got                  # read once a run
    other = dict(red, busy_s=red["busy_s"] / 2)
    assert P.program({"trace": other, "chips": 1}) is None
    assert "none of the 1 trace files" in capsys.readouterr().err
    # the run's own file, read by a window() that has drifted
    real = P.window
    monkeypatch.setattr(P, "window", lambda dev, host, devs=1: dict(
        real(dev, host, devs=devs), busy_s=0.0))
    assert P.program({"trace": red, "chips": 1}) is None
    assert "no longer computes" in capsys.readouterr().err


def test_recorded_train_trace_keeps_its_reduction():
    """The reduction the accepted metrics read is unchanged on the
    recorded trace (one second of lm144m-train-4k, 7 steps, before the
    kernels were named)."""
    r = R.reduce(TRAIN)
    assert r["window_s"] == pytest.approx(1.648408601, abs=1e-12)
    assert r["busy_s"] == pytest.approx(1.646637942, abs=1e-12)
    assert r["programs"]["count"] == 7
    assert r["programs"]["seconds"] == pytest.approx(1.646711458,
                                                     abs=1e-12)
    assert len(r["op_seconds"]) == 1378
    assert r["idle_by_span"]["train.wait"] == pytest.approx(
        0.001286816, abs=1e-12)
    # 144 Mosaic launches a step, none named yet
    ks = P.kernel_seconds(r["op_seconds"])
    assert ks == {"unnamed": pytest.approx(0.412360313, abs=1e-12)}
    got = P.reduce(TRAIN)
    assert got["idle_by_program_span"] == {
        P.NO_SPAN: pytest.approx(r["idle_s"], abs=1e-15)}


def test_recorded_decode_trace():
    """Half a second of yi6b-decode-8k (10 ticks) on a TPU v5e, recorded
    with the engine's spans and named kernels: every kernel carries its
    family, and the engine's spans hold the device's idle time."""
    path = os.path.join(DATA, "decode_v5e.xplane.pb")
    dev, host = R.events(path)
    red = R.reduce_events(dev, host)
    ks = P.kernel_seconds(red["op_seconds"])
    assert set(ks) == {"decode_attend_paged", "decode_update_paged"}
    assert {"serve.tick", "serve.admit", "serve.prepare", "serve.tables",
            "serve.decode", "serve.sample", "serve.readback",
            "serve.bookkeep"} <= {n for n, _, _ in host}
    gaps = P.window(dev, host)["gaps"]
    idle = P.idle_by_program_span(gaps, host)
    assert sum(idle.values()) == pytest.approx(red["idle_s"])
    assert idle[P.NO_SPAN] < 0.005 * red["idle_s"]
    # a gap over 1 ms runs from one tick's read-back into the next tick;
    # only the harness's loop between the two lies outside the engine
    for g in gaps:
        if g[1] - g[0] > 1e6:
            split = P.idle_by_program_span([g], host)
            assert split.get(P.NO_SPAN, 0.0) < 0.01 * (g[1] - g[0]) * 1e-9
