"""The program's spans as the benchmark reads them (``bench/spans.py``): a
tiny slow restore, decode step and synchronous dump traced on the CPU; the
idle-gap reduction with program phases; and the per-layer metrics that read
them in a traced tiny cell of each traffic mix."""
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import spans as bspans
from bench import trace as btrace
from bench.common import load_module
from tiny_cell import ROOT, add_tiny_cell, run_tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_METRICS = {
    "olmo-1b.mcts-swe": ["restore_wait_ms.mcts", "restore_decode_ms.mcts", "restore_upload_ms.mcts",
                         "dump_queue_ms.mcts"],
    "qwen2-vl-2b.mcts-swe-8k": ["restore_wait_ms.mcts", "restore_decode_ms.mcts",
                                "restore_upload_ms.mcts", "dump_queue_ms.mcts"],
    "olmo-1b.rl-fanout": ["sample_ms.fanout", "dump_ms.fanout", "dump_backlog.fanout"],
}


def _trace_file(logdir):
    return bspans.trace_file(types.SimpleNamespace(root=logdir, cell={"name": "x"}))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced window over a tiny served sandbox: two checkpoints with
    synchronous dumps (the second over the first), a decode step, a
    lightweight checkpoint, a slow restore of the lightweight one (the
    second image over the first one's generation, then a replay), a slow
    restore of the first checkpoint, and a demoted wait at a dump gate."""
    from repro.configs import get_config
    from repro.core import DeltaCR, DeltaFS, Sandbox, StateManager
    from repro.core.stream import DumpGate
    from repro.models import Model
    from repro.serve import Engine, PagedSession, PagePool

    cfg = get_config("olmo-1b-tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagePool(cfg, num_pages=64, page_size=8, max_pages_per_session=16)
    eng = Engine(model, params, pool)
    sess = eng.new_session(list(range(1, 20)))
    fs = DeltaFS(chunk_bytes=1024)
    fs.write("repo/a.py", np.arange(64, dtype=np.uint8))
    cr = DeltaCR(store=fs.store, async_warm=False,
                 restore_fn=lambda p: PagedSession.restore_from_payload(pool, p))
    sm = StateManager(Sandbox(fs, sess), cr)
    sm.action_applier = lambda sb, a: None
    c0 = sm.checkpoint()
    cr.wait_dumps()                     # the first image's dump compiles outside the window
    eng.step([sm.sandbox.proc])
    logdir = str(tmp_path_factory.mktemp("trace") / ".bench_trace" / "x")
    with jax.profiler.trace(logdir):
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.step([sm.sandbox.proc])
            c1 = sm.checkpoint()
            cr.wait_dumps()
            lw = sm.checkpoint(lightweight=True, actions=("noop",))
            eng.step([sm.sandbox.proc])
            cr.evict_template(c0)
            cr.evict_template(c1)
            paths = [sm.restore(lw), sm.restore(c0)]
            gate = DumpGate(1, demote_max_ms=2.0)
            gate.set_runnable(1)
            gate.acquire("bg")
            gate.release()
    images = {c: cr.dump_future(c).result() for c in (c0, c1)}
    sm.sandbox.proc.release()
    cr.shutdown()
    path = _trace_file(os.path.dirname(os.path.dirname(logdir)))
    return {"path": path, "prog": bspans.load(path), "ckpts": (c0, c1, lw), "cr": cr.span_id,
            "paths": paths, "images": images}


def test_every_program_span_is_found_with_its_args(traced):
    spans = bspans.in_window(traced["prog"])
    by = {}
    for sp in spans:
        by.setdefault(sp["name"], []).append(sp)
    c0, c1, lw = traced["ckpts"]
    assert traced["paths"] == ["slow+replay", "slow"]
    want = {
        "engine.step", "engine.plan", "engine.build", "engine.dispatch", "engine.absorb",
        "engine.fetch", "engine.sample",
        "statemgr.checkpoint", "statemgr.fs_freeze", "statemgr.restore", "statemgr.fs_switch",
        "statemgr.replay",
        "deltacr.checkpoint", "deltacr.template_fork", "deltacr.dump_fork", "deltacr.submit",
        "deltacr.restore", "deltacr.image_wait", "deltacr.decode", "deltacr.apply",
        "deltacr.read", "deltacr.keep_base", "deltacr.upload", "deltacr.admit",
        "dump", "dump.parent_wait", "dump.encode", "dump.drain", "dump.commit", "dump.gate_wait",
    }
    assert want <= set(by), want - set(by)
    assert {sp["args"]["batch"] for sp in by["engine.step"]} == {1}
    assert all("pages" in sp["args"] for sp in by["engine.plan"])
    # one checkpoint's spans joined across threads by (cr, ckpt)
    key = (traced["cr"], c1)
    ck = [sp for sp in by["deltacr.checkpoint"] if (sp["args"]["cr"], sp["args"]["ckpt"]) == key]
    dump = [sp for sp in by["dump"] if (sp["args"]["cr"], sp["args"]["ckpt"]) == key]
    assert len(ck) == 1 and ck[0]["args"]["dump"] == 1 and ck[0]["main"]
    assert len(dump) == 1 and not dump[0]["main"]
    assert dump[0]["args"]["mode"] == traced["images"][c1].mode
    assert dump[0]["args"]["bytes"] == traced["images"][c1].dump_bytes
    assert dump[0]["args"]["queued_ms"] >= 0.0
    assert [sp["args"]["ckpt"] for sp in by["statemgr.checkpoint"]] == [c1, lw]
    # the slow restores: statemgr over deltacr over the phases, on one thread
    assert [(sp["args"]["ckpt"], sp["args"]["path"]) for sp in by["statemgr.restore"]] == [
        (lw, "slow+replay"), (c0, "slow")]
    rs = by["deltacr.restore"]
    assert [(sp["args"]["cr"], sp["args"]["ckpt"], sp["args"]["path"]) for sp in rs] == [
        (traced["cr"], c1, "slow"), (traced["cr"], c0, "slow")]
    decode = by["deltacr.decode"][0]
    assert decode["args"]["rows"] == traced["images"][c1].dirtied_chunks
    assert decode["args"]["bytes"] > 0
    assert by["deltacr.upload"][0]["args"]["pages"] > 0
    for name in ("deltacr.image_wait", "deltacr.decode", "deltacr.upload", "deltacr.admit"):
        inner = by[name][0]
        assert inner["main"] and rs[0]["start"] <= inner["start"] and inner["end"] <= rs[0]["end"]
    apply = by["deltacr.apply"][0]
    assert decode["start"] <= apply["start"] and apply["end"] <= decode["end"]
    # c0 has no parent image: its decode reads every chunk from the store
    full = by["deltacr.decode"][1]
    for name in ("deltacr.read", "deltacr.keep_base"):
        assert any(full["start"] <= sp["start"] and sp["end"] <= full["end"] for sp in by[name])


def test_sync_dump_stage_timers_match_the_spans(traced):
    """The synchronous dump's stage timers are read at the boundaries of its
    ``dump.encode`` / ``drain`` / ``commit`` spans."""
    spans = bspans.in_window(traced["prog"])
    image = traced["images"][traced["ckpts"][1]]
    dump = next(sp for sp in spans
                if sp["name"] == "dump" and sp["args"]["ckpt"] == traced["ckpts"][1])
    assert not image.streamed
    for stage, timer in (("encode", image.encode_ms), ("drain", image.drain_ms),
                         ("commit", image.commit_ms)):
        inside = [sp for sp in spans if sp["name"] == f"dump.{stage}"
                  and dump["start"] <= sp["start"] and sp["end"] <= dump["end"]]
        assert inside and timer > 0.0
        assert timer == pytest.approx(sum(bspans.ms(sp) for sp in inside), abs=0.5)


def test_idle_by_phase_reads_as_reduce_without_program_spans():
    """On the recorded chip slice (a program from before spans), the
    reduction with program phases gives exactly ``bench.trace.reduce``."""
    for name in sorted(f for f in os.listdir(DATA) if f.endswith(".json")):
        trace = json.load(open(os.path.join(DATA, name)))
        empty = {"window": None, "window_thread": None, "spans": []}
        assert bspans.idle_by_phase(trace, empty) == btrace.reduce(trace)


def test_gaps_go_to_the_window_threads_innermost_span():
    """Each idle gap goes to the innermost span over its midpoint on the
    window's thread (the rule of ``bench.trace.reduce``); the dump worker's
    spans, open over the whole window, take none."""
    ops = [[100.0, 100.0], [390.0, 10.0], [480.0, 20.0], [700.0, 40.0], [760.0, 240.0]]
    trace = {
        "devices": {"/device:TPU:0": [["op", s, d, "jit_step"] for s, d in ops]},
        "host": [["bench.window", 0.0, 1000.0], ["bench.rollback", 200.0, 600.0]],
    }
    main, worker = "/host:CPU#1", "/host:CPU#0"
    prog = {
        "window": [0.0, 1000.0], "window_thread": main,
        "spans": [
            ["deltacr.restore", 210.0, 580.0, main, {}],
            ["deltacr.decode", 220.0, 280.0, main, {}],
            ["deltacr.apply", 400.0, 80.0, main, {}],
            ["deltacr.upload", 500.0, 200.0, main, {}],
            ["dump", 0.0, 1000.0, worker, {}],
            ["dump.encode", 300.0, 100.0, worker, {}],
        ],
    }
    gaps = bspans.idle_by_phase(trace, prog)["idle_gaps"]
    assert gaps == pytest.approx({
        "host: outside benchmark spans": 100e-9,     # 0..100
        "deltacr.decode": 190e-9,                    # 200..390
        "deltacr.apply": 80e-9,                      # 400..480
        "deltacr.upload": 200e-9,                    # 500..700
        "deltacr.restore": 20e-9,                    # 740..760: the restore's own time
    })


def test_readers_read_nothing_from_a_program_without_spans(tmp_path):
    """A traced window of a program that emits no span: every new reader
    returns None and raises nothing."""
    logdir = tmp_path / ".bench_trace" / "x"
    with jax.profiler.trace(str(logdir)):
        with jax.profiler.TraceAnnotation("bench.window"):
            jax.numpy.ones(4).sum().block_until_ready()
    run = types.SimpleNamespace(root=str(tmp_path), cell={"name": "x"}, trace=True)
    for names in NEW_METRICS.values():
        for m in names:
            mod = load_module(os.path.join(ROOT, "bench", "metrics", m + ".py"))
            assert mod.read(run) is None, m


@pytest.mark.parametrize("source", sorted(NEW_METRICS))
def test_traced_tiny_cell_reports_program_metrics(source, tmp_path, capsys, monkeypatch):
    """Every new metric reads a number in a traced tiny run; the fan-out's
    backlog from spans agrees with the dump futures left at the window's end."""
    import bench.drivers.fanout as fanout

    at_end = {}
    window = fanout.window

    def counted(run, seconds):
        window(run, seconds)
        futs = [run.trees[i].cr.dump_future(c) for i, c in run.ckpt_ids]
        at_end["backlog"] = sum(1 for f in futs if f is not None and not f.done())

    monkeypatch.setattr(fanout, "window", counted)
    name = add_tiny_cell(str(tmp_path), source)
    res = run_tiny(str(tmp_path), name, capsys, monkeypatch, trace=1)
    assert res["correct"] is True, res["checks"]
    for m in NEW_METRICS[source]:
        assert m in res["metrics"], m
    if "dump_backlog.fanout" in res["metrics"]:
        assert abs(res["metrics"]["dump_backlog.fanout"]["value"] - at_end["backlog"]) <= 2
