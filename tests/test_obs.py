"""Telemetry subsystem tests (ISSUE 10).

The obs contract: spans nest and time monotonically (device-synced at
exit), the metrics registry has exact counter/histogram semantics and
mirrors ``CommLedger.summary()`` bit-for-bit, the event log round-trips
through JSONL on the same timeline as the trace, and — the load-bearing
half — the DISABLED path mutates nothing and never retraces a compiled
program (the jit cache-miss hook sees zero new traces on warm calls).
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.membership_engine import MembershipConfig, MembershipEngine
from repro.core.oneshot import CommLedger, one_shot_clustering


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with telemetry off and empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------- spans

class TestSpans:
    def test_nesting_parent_child_depth(self):
        with obs.scope(True):
            with obs.span("outer", impl="dense") as outer:
                with obs.span("inner") as inner:
                    pass
                with obs.span("inner2"):
                    pass
        recs = {r["name"]: r for r in obs.trace_records()}
        assert set(recs) == {"outer", "inner", "inner2"}
        assert recs["outer"]["parent"] == 0 and recs["outer"]["depth"] == 0
        assert recs["inner"]["parent"] == recs["outer"]["id"]
        assert recs["inner2"]["parent"] == recs["outer"]["id"]
        assert recs["inner"]["depth"] == 1
        assert recs["outer"]["meta"] == {"impl": "dense"}
        del outer, inner

    def test_timing_monotonic_and_contained(self):
        with obs.scope(True):
            with obs.span("outer"):
                with obs.span("inner"):
                    float(jnp.ones(64).sum())  # some real work
        recs = {r["name"]: r for r in obs.trace_records()}
        o, i = recs["outer"], recs["inner"]
        assert o["dur_us"] >= 0 and i["dur_us"] >= 0
        # child starts no earlier than parent and fits inside it
        assert i["ts_us"] >= o["ts_us"]
        assert i["ts_us"] + i["dur_us"] <= o["ts_us"] + o["dur_us"] + 1e-3
        # records share one monotonic epoch: successive spans don't step back
        with obs.scope(True):
            with obs.span("later"):
                pass
        later = [r for r in obs.trace_records() if r["name"] == "later"][0]
        assert later["ts_us"] >= o["ts_us"]

    def test_sync_blocks_device_values(self):
        with obs.scope(True):
            with obs.span("compute") as sp:
                out = sp.sync(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
        assert float(out[0, 0]) == 256.0
        rec = obs.trace_records()[-1]
        assert rec["name"] == "compute" and rec["dur_us"] > 0

    def test_wait_us_on_a_synced_span(self):
        with obs.scope(True):
            with obs.span("compute") as sp:
                sp.sync(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
        rec = obs.trace_records()[-1]
        assert "wait_us" in rec
        assert 0.0 <= rec["wait_us"] <= rec["dur_us"]

    def test_no_wait_us_without_registered_values(self):
        with obs.scope(True):
            with obs.span("host_only"):
                float(jnp.ones(8).sum())
            with obs.span("unsynced", sync=False) as sp:
                sp.sync(jnp.ones(8))
        for rec in obs.trace_records():
            assert "wait_us" not in rec, rec["name"]

    def test_note_attaches_meta(self):
        with obs.scope(True):
            with obs.span("s") as sp:
                sp.note(rounds=3, backend="jnp")
        rec = obs.trace_records()[-1]
        assert rec["meta"] == {"rounds": 3, "backend": "jnp"}

    def test_threads_get_independent_stacks(self):
        def worker():
            with obs.span("worker.outer"):
                with obs.span("worker.inner"):
                    pass

        with obs.scope(True):
            with obs.span("main.outer"):
                t = threading.Thread(target=worker, name="obs-worker")
                t.start()
                t.join()
        recs = {r["name"]: r for r in obs.trace_records()}
        # the thread's root span must NOT be parented under main.outer
        assert recs["worker.outer"]["parent"] == 0
        assert recs["worker.inner"]["parent"] == recs["worker.outer"]["id"]
        assert recs["worker.outer"]["thread"] == "obs-worker"

    def test_jsonl_round_trip_and_tree(self, tmp_path):
        with obs.scope(True):
            with obs.span("root", impl="x"):
                with obs.span("leaf"):
                    pass
        p = obs.save_trace(tmp_path / "trace.jsonl")
        loaded = obs.load_trace(p)
        assert loaded == obs.trace_records()
        tree = obs.format_tree(loaded)
        root_line, leaf_line = tree.splitlines()
        assert root_line.startswith("root") and "impl=x" in root_line
        assert leaf_line.startswith("  leaf")     # indented under root

    def test_format_tree_empty(self):
        assert obs.format_tree([]) == "(no spans recorded)"


# -------------------------------------------------------------- metrics

class TestMetrics:
    def test_counter_semantics(self):
        with obs.scope(True):
            obs.count("c")
            obs.count("c", 4)
            obs.count("c", kernel="assign")
            obs.count("c", 2, kernel="assign")
            obs.count("c", kernel="hac")
        assert obs.counter_value("c") == 5
        assert obs.counter_value("c", kernel="assign") == 3
        assert obs.counter_value("c", kernel="hac") == 1
        assert obs.counter_total("c") == 9

    def test_gauge_last_value_wins(self):
        with obs.scope(True):
            obs.gauge("g", 1.5)
            obs.gauge("g", jnp.asarray(2.5))   # device scalar coerced
            obs.gauge("plan", "bm=32,bn=64", kernel="assign")
        assert obs.gauge_value("g") == 2.5
        assert isinstance(obs.gauge_value("g"), float)
        assert obs.gauge_value("plan", kernel="assign") == "bm=32,bn=64"

    def test_histogram_semantics(self):
        with obs.scope(True):
            for v in (0.5, 1.0, 3.0, 100.0):
                obs.observe("h", v)
        h = obs.snapshot()["histograms"]["h"]
        assert h["count"] == 4
        assert h["total"] == pytest.approx(104.5)
        assert h["min"] == 0.5 and h["max"] == 100.0
        assert h["mean"] == pytest.approx(104.5 / 4)
        # pow-2 buckets: <=1 -> "1", 3 -> "4", 100 -> "128"
        assert h["buckets"] == {"1": 2, "4": 1, "128": 1}

    def test_snapshot_diff(self):
        with obs.scope(True):
            obs.count("a")
            obs.gauge("g", 1)
            before = obs.snapshot()
            obs.count("a", 2)
            obs.count("b")
            obs.gauge("g", 7)
            obs.observe("h", 10.0)
            after = obs.snapshot()
        d = obs.diff(before, after)
        assert d["counters"] == {"a": 2, "b": 1}
        assert d["gauges"] == {"g": [1, 7]}
        assert d["histograms"] == {"h": {"count": 1, "total": 10.0}}
        # identical snapshots diff to nothing
        assert not any(obs.diff(after, after).values())

    def test_snapshot_round_trip(self, tmp_path):
        with obs.scope(True):
            obs.count("a", 3)
            obs.observe("h", 2.0)
        p = obs.save_snapshot(tmp_path / "snap.json")
        assert obs.load_snapshot(p) == obs.snapshot()

    def test_ledger_parity_vs_summary(self):
        """comm.* gauges mirror CommLedger.summary() exactly — the
        telemetry view of the paper's communication-cost claim."""
        ledger = CommLedger(n_users=40, d=16, top_k=6,
                            model_params=10_000, mode="streaming")
        with obs.scope(True):
            obs.record_ledger(ledger)
        s = ledger.summary()
        for k, v in s.items():
            if v is None:
                continue
            assert obs.gauge_value(f"comm.{k}") == v, k
        assert (obs.gauge_value("comm_upload_bytes")
                == s["per_user_upload_bytes"] * s["n_users"])

    def test_ledger_none_fields_skipped(self):
        ledger = CommLedger(n_users=8, d=4, top_k=2)  # model_params=0
        assert ledger.summary()["oneshot_vs_iterative_ratio"] is None
        with obs.scope(True):
            obs.record_ledger(ledger)
        assert obs.gauge_value("comm.oneshot_vs_iterative_ratio") is None


# ------------------------------------------------------------- disabled

class TestDisabledMode:
    def test_span_is_shared_noop(self):
        s1 = obs.span("a", impl="x")
        s2 = obs.span("b")
        assert s1 is s2                       # one shared object, no alloc
        with s1 as sp:
            v = sp.sync(jnp.ones(3))
            sp.note(k=1)
        assert v.shape == (3,)
        assert obs.trace_records() == []

    def test_zero_registry_mutation(self):
        obs.count("c")
        obs.gauge("g", 1)
        obs.observe("h", 2.0)
        obs.event("kind", x=1)
        obs.record_ledger(CommLedger(n_users=4, d=2, top_k=1))
        snap = obs.snapshot()
        assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
        assert obs.events() == []

    def test_new_spans_record_nothing_and_read_no_clock(
            self, oneshot_result, monkeypatch):
        """The lifecycle's host reads and a fused training job, with
        telemetry off: no span, and no clock read by the obs layer."""
        from repro.obs import core

        def no_clock():
            raise AssertionError("obs read the clock while disabled")

        monkeypatch.setattr(core, "now", no_clock)
        monkeypatch.setattr(obs, "now", no_clock)
        eng, lam, v = _device_wave(oneshot_result)
        slots = eng.admit(lam, v, eng.assign(lam, v).labels)
        eng.evict(slots[:2])
        eng.drift_stats()
        _tiny_fused_job()
        assert obs.trace_records() == []
        assert obs.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}

    def test_scope_restores_prior_state(self):
        assert not obs.enabled()
        with obs.scope(True):
            assert obs.enabled()
            with obs.scope(False):
                assert not obs.enabled()
            assert obs.enabled()
        assert not obs.enabled()

    def test_toggling_never_retraces(self):
        """The retrace guarantee: a function jitted with telemetry off is
        NOT recompiled when telemetry turns on (and vice versa), because
        the disabled path does no work inside jit boundaries."""
        @jax.jit
        def f(x):
            return (x * 2).sum()

        x = jnp.ones(17)                       # distinctive shape
        f(x).block_until_ready()               # warm with obs off
        with obs.scope(True):
            before = obs.counter_value("retrace_count")
            for _ in range(3):
                f(x).block_until_ready()       # warm calls, obs on
            assert obs.counter_value("retrace_count") == before
            f(jnp.ones((17, 2))).block_until_ready()   # genuinely new shape
            assert obs.counter_value("retrace_count") > before


# --------------------------------------------------------------- events

class TestEvents:
    def test_order_and_fields(self):
        with obs.scope(True):
            obs.event("admit", n=3, slots=[0, 1, 2])
            obs.event("evict", n=1)
        evs = obs.events()
        assert [e["kind"] for e in evs] == ["admit", "evict"]
        assert evs[0]["seq"] < evs[1]["seq"]
        assert evs[0]["t_us"] <= evs[1]["t_us"]
        assert evs[0]["n"] == 3 and evs[0]["slots"] == [0, 1, 2]

    def test_device_scalars_coerced(self):
        with obs.scope(True):
            obs.event("e", frac=jnp.asarray(0.25), n=np.int64(7))
        e = obs.events("e")[0]
        assert e["frac"] == 0.25 and isinstance(e["frac"], float)
        assert e["n"] == 7 and isinstance(e["n"], int)
        json.dumps(e)                          # JSON-able end to end

    def test_kind_filter(self):
        with obs.scope(True):
            obs.event("a")
            obs.event("b")
            obs.event("a")
        assert len(obs.events("a")) == 2
        assert len(obs.events("b")) == 1

    def test_jsonl_round_trip(self, tmp_path):
        with obs.scope(True):
            obs.event("admit", n=2)
            obs.event("recluster", label_agreement=0.75)
        p = obs.save_events(tmp_path / "events.jsonl")
        assert obs.load_events(p) == obs.events()


# --------------------------------------------- instrumented hot paths

@pytest.fixture(scope="module")
def oneshot_result():
    rng = np.random.default_rng(0)
    feats = [rng.normal(size=(24, 8)).astype(np.float32) for _ in range(12)]
    return one_shot_clustering(feats, 2)


def _device_wave(res):
    """A jnp directory seeded from ``res`` and a 4-newcomer wave held on
    the device, as a serving loop holds its uploads."""
    eng = MembershipEngine.from_oneshot(
        res, MembershipConfig(backend="jnp", capacity=32))
    return eng, jnp.asarray(res.lam)[:4], jnp.asarray(res.v)[:4]


ROUNDS = 3


def _tiny_fused_job(scan_rounds=False):
    """A fused MT-HFL job of ROUNDS rounds: 2 clusters of 2 MLP users."""
    from repro.data.partition import UserData
    from repro.fed import client as fclient
    from repro.fed import partition as fpart
    from repro.fed import trainer as ftrainer
    from repro.models import mlp

    rng = np.random.default_rng(0)
    mcfg = mlp.PaperMLPConfig(m=6, hidden=4, n_classes=2)
    users = [UserData(user_id=i, task_id=i // 2,
                      x=rng.normal(size=(12, 6)).astype(np.float32),
                      y=rng.integers(0, 2, 12).astype(np.int32),
                      task_classes=(0, 1)) for i in range(4)]
    model = ftrainer.TaskModel(
        init=lambda k: mlp.init(mcfg, k), loss_fn=mlp.loss_fn(mcfg),
        accuracy=lambda p, x, y: mlp.accuracy(mcfg, p, x, y),
        is_common=fpart.prefix_predicate(mlp.COMMON_PREFIXES))
    evals = [(rng.normal(size=(8, 6)).astype(np.float32),
              rng.integers(0, 2, 8).astype(np.int32))] * 2
    cfg = ftrainer.MTHFLConfig(global_rounds=ROUNDS, local_rounds=1,
                               local_steps=2, batch_size=4,
                               client=fclient.ClientConfig(lr=0.1),
                               scan_rounds=scan_rounds)
    return ftrainer.train_mthfl(users, np.asarray([0, 0, 1, 1]),
                                [model, model], evals, cfg,
                                cluster_classes=[[0, 1], [0, 1]],
                                fused=True)


class TestInstrumentation:
    def test_pipeline_emits_all_three_pillars(self, oneshot_result):
        obs.reset()
        res = oneshot_result
        with obs.scope(True):
            eng = MembershipEngine.from_oneshot(
                res, MembershipConfig(backend="jnp", capacity=32))
            lam = np.asarray(res.lam)[:4]
            v = np.asarray(res.v)[:4]
            wave = eng.assign(lam, v)
            eng.admit(lam, v, np.asarray(wave.labels))
            eng.drift_stats()
        names = {r["name"] for r in obs.trace_records()}
        assert {"membership.assign", "membership.admit"} <= names
        assert obs.counter_value("membership.assign_waves") == 1
        assert obs.counter_value("membership.admits") == 4   # members
        assert obs.gauge_value("directory_bytes") > 0
        assert obs.gauge_value("unassigned_frac") is not None
        assign = [r for r in obs.trace_records()
                  if r["name"] == "membership.assign"]
        assert len(assign) == 1
        assert 0.0 <= assign[0]["wait_us"] <= assign[0]["dur_us"]
        kinds = [e["kind"] for e in obs.events()]
        assert kinds == ["seed", "assign_wave", "admit"]
        wave_ev = obs.events("assign_wave")[0]
        assert wave_ev["n"] == 4

    def test_trainer_spans_per_round_and_per_job(self):
        with obs.scope(True):
            _tiny_fused_job()
        recs = obs.trace_records()
        by = {}
        for r in recs:
            by.setdefault(r["name"], []).append(r)
        assert len(by["trainer.round"]) == ROUNDS
        assert len(by["trainer.eval"]) == ROUNDS
        assert len(by["trainer.restack"]) == 1
        assert len(by["trainer.setup"]) == 1
        job = by["trainer.train_mthfl"][0]
        assert job["meta"]["fused"] is True
        rounds_id = by["trainer.rounds"][0]["id"]
        for r in by["trainer.round"] + by["trainer.eval"]:
            assert r["parent"] == rounds_id
        for r in by["trainer.setup"] + by["trainer.restack"]:
            assert r["parent"] == job["id"]
        for r in by["trainer.round"] + by["trainer.restack"]:
            assert 0.0 <= r["wait_us"] <= r["dur_us"]

    def test_scanned_job_evaluates_every_round(self):
        with obs.scope(True):
            _tiny_fused_job(scan_rounds=True)
        names = [r["name"] for r in obs.trace_records()]
        assert names.count("trainer.eval") == ROUNDS
        assert names.count("trainer.scan_rounds") == 1

    def test_fused_programs_carry_their_names(self, monkeypatch):
        """The fused round and the scanned run compile under their own
        names, so a profile names them (not ``jit__unknown``)."""
        from repro.fed import trainer as ftrainer

        seen = {}
        for attr in ("_fused_global_round", "_fused_run"):
            orig = getattr(ftrainer, attr)

            def spy(*args, _orig=orig, _attr=attr, **kw):
                seen[_attr] = _orig.lower(*args, **kw).as_text()
                return _orig(*args, **kw)

            monkeypatch.setattr(ftrainer, attr, spy)
        _tiny_fused_job()
        _tiny_fused_job(scan_rounds=True)
        assert "module @jit__fused_global_round" in seen[
            "_fused_global_round"]
        assert "module @jit__fused_run" in seen["_fused_run"]

    def test_lifecycle_host_reads_named(self, oneshot_result, monkeypatch):
        """One admit -> evict -> drift_stats cycle: every blocking device
        read is a ``membership.host_read`` span under its lifecycle
        span, named by what it read; admit and evict each dispatch one
        program, under its own name."""
        from repro.core import membership_engine as me

        eng, lam, v = _device_wave(oneshot_result)
        labels = eng.assign(lam, v).labels
        calls = {}
        for attr in ("_admit_program", "_evict_program"):
            orig = getattr(me, attr)

            def spy(*args, _orig=orig, _attr=attr, **kw):
                calls.setdefault(_attr, []).append(
                    _orig.lower(*args, **kw).as_text())
                return _orig(*args, **kw)

            monkeypatch.setattr(me, attr, spy)
        obs.reset()
        with obs.scope(True):
            slots = eng.admit(lam, v, labels)
            eng.evict(slots[:2])
            stats = eng.drift_stats()
        recs = obs.trace_records()
        ids = {r["id"]: r["name"] for r in recs}
        reads = {}
        for r in recs:
            if r["name"] == "membership.host_read":
                reads.setdefault(ids[r["parent"]], []).append(
                    r["meta"]["what"])
        assert reads == {
            "membership.admit": ["slots"],
            "membership.evict": ["evict_ok"],
            "membership.drift_stats": ["valid", "labels", "protos",
                                       "protos0"]}
        assert [len(calls[a]) for a in ("_admit_program",
                                         "_evict_program")] == [1, 1]
        assert "module @jit__admit_program" in calls["_admit_program"][0]
        assert "module @jit__evict_program" in calls["_evict_program"][0]
        for r in recs:
            if r["name"] in ("membership.admit", "membership.evict"):
                assert 0.0 <= r["wait_us"] <= r["dur_us"]
        assert stats["n_members"] == len(oneshot_result.labels) + 2

    def test_oneshot_records_ledger_and_spans(self):
        rng = np.random.default_rng(1)
        feats = [rng.normal(size=(16, 6)).astype(np.float32)
                 for _ in range(8)]
        obs.reset()
        with obs.scope(True):
            res = one_shot_clustering(feats, 2)
        names = {r["name"] for r in obs.trace_records()}
        assert {"oneshot.run", "protocol.run", "cluster.hac"} <= names
        assert (obs.gauge_value("comm.per_user_upload_bytes")
                == res.ledger.summary()["per_user_upload_bytes"])

    def test_tile_resolution_counts_dispatches(self):
        from repro.kernels import tuning

        with obs.scope(True):
            blocks = tuning.get_blocks("assign", b=64, d2=96)
            tuning.get_blocks("assign", b=64, d2=96)
        assert blocks                          # a real tile plan came back
        assert obs.counter_value("dispatch_count") == 2
        assert obs.counter_value("kernel_calls", kernel="assign") == 2
        assert obs.gauge_value("kernel_blocks",
                               kernel="assign") is not None

    def test_disabled_pipeline_identical_and_silent(self, oneshot_result):
        """Same workload with telemetry off: same verdicts, empty obs."""
        obs.reset()
        res = oneshot_result
        eng = MembershipEngine.from_oneshot(
            res, MembershipConfig(backend="jnp", capacity=32))
        lam = np.asarray(res.lam)[:4]
        v = np.asarray(res.v)[:4]
        wave = eng.assign(lam, v)
        with obs.scope(True):
            eng2 = MembershipEngine.from_oneshot(
                res, MembershipConfig(backend="jnp", capacity=32))
            wave2 = eng2.assign(lam, v)
        np.testing.assert_array_equal(np.asarray(wave.labels),
                                      np.asarray(wave2.labels))
        # the disabled half left nothing behind but the enabled half did
        assert any(r["name"] == "membership.assign"
                   for r in obs.trace_records())
        assert obs.counter_value("membership.assign_waves") == 1

    def test_stamp_shape(self):
        s = obs.stamp()
        assert set(s) == {"obs_enabled", "dispatch_count", "retrace_count"}
        assert s["obs_enabled"] is False
