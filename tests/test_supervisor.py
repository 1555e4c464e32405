"""Crash-contained multi-process serving (``sph/supervisor.py`` +
``sph/worker.py`` + the resilient client).

The contract under test:

  * a REAL SIGKILL of an engine worker mid-request is invisible to the
    request's outcome: the supervisor restarts the worker, the lane
    resumes from its last block checkpoint, and the final state is
    BIT-IDENTICAL to an uninterrupted solo run;
  * a sibling shape bucket on another device's worker streams through
    the whole episode untouched (no recovering event, bit-identical
    state) and the frontend process never exits;
  * the pool holds one worker per device: buckets spread over the
    devices, never more workers than devices, two buckets on a
    one-device host share one worker;
  * the restarted worker reclaims its dead predecessor's lockfiles
    QUIETLY — one summary line, no per-lane warning spam;
  * ``--max-restarts`` exhaustion answers RETRY_AFTER with a resume
    token that a later resubmission (fresh worker, fresh restart
    budget) completes from the checkpoint;
  * ``client.run_request_resilient`` survives RETRY_AFTER-with-token
    and mid-stream EOF without manual intervention (unit-tested against
    an in-process fake server — no JAX).
"""
import socket
import threading
import time

import numpy as np
import pytest

import chaos
from repro.checkpoint.manager import _flatten
from repro.core import ensemble, recovery
from repro.core.api import Simulation
from repro.core.cases import resolve_ds
from repro.sph import client
from repro.sph.serve import recv_frame, request_key, send_frame, worker_tag
from repro.sph import supervisor
from repro.sph.supervisor import FrontendServer

BLOCK = 8
POLICY = recovery.GuardPolicy(block=BLOCK, snapshot_every=1)


def _solo_state(n: int, nsteps: int):
    sim = Simulation.from_case(
        "taylor_green", ds=resolve_ds("taylor_green", n))
    mcfg = ensemble.member_config(sim.cfg, POLICY)
    state, _, report, _ = recovery.run_guarded(
        mcfg, sim.state, nsteps, POLICY)
    assert not report.recovered
    return {k: np.asarray(v) for k, v in _flatten(state).items()}


def _assert_state_equal(done_frame, want, label):
    got = client.final_state(done_frame)
    assert set(got) == set(want), label
    for k in want:
        assert np.array_equal(got[k], want[k]), (label, k)


class TestRouting:
    def test_request_key_buckets_by_case_and_overrides(self):
        a = {"case": "taylor_green", "n": 100, "nsteps": 16}
        b = {"case": "taylor_green", "n": 150, "nsteps": 16}
        c = {"case": "taylor_green", "n": 100, "nsteps": 999,
             "observe": True}
        assert request_key(a) != request_key(b)  # resolution = bucket
        assert request_key(a) == request_key(c)  # nsteps/flags don't
        assert worker_tag(a) != worker_tag(b)
        assert worker_tag(a).startswith("taylor_green-")


class _PoolProbe(FrontendServer):
    """A frontend whose spawns are recorded, not run (no worker
    processes, no JAX) — the routing half of the pool under test."""

    def _spawn(self, h):
        self.spawned.append(h.chip)


def _pool(tmp_path, chips):
    srv = _PoolProbe(port=0, checkpoint_dir=str(tmp_path),
                     devices=(chips, "tpu"))
    srv.spawned = []
    return srv


def _close(srv):
    srv.stopped.set()
    srv.ipc_sock.close()
    srv.lsock.close()


def _req(case, n):
    return {"case": case, "n": n}


class TestWorkerPool:
    def test_one_chip_two_buckets_one_worker(self, tmp_path):
        srv = _pool(tmp_path, 1)
        try:
            for r in (_req("taylor_green", 100), _req("dam_break", 100),
                      _req("taylor_green", 100)):
                srv._ensure_worker(request_key(r), worker_tag(r))
            assert srv.spawned == [0]
            assert list(srv.workers) == [0]
            assert len(srv.workers[0].buckets) == 2
            st = srv._extra_stats()
            assert st["chips"] == 1 and st["buckets"] == 2
            assert len(st["workers"]) == 1
        finally:
            _close(srv)

    def test_buckets_spread_never_more_workers_than_chips(self, tmp_path):
        srv = _pool(tmp_path, 4)
        try:
            reqs = [_req("taylor_green", n) for n in (100, 120, 140)] + [
                _req("dam_break", n) for n in (100, 120, 140)]
            chips = [srv._ensure_worker(request_key(r), worker_tag(r)).chip
                     for r in reqs]
            # least-loaded first: four buckets fill four chips, then wrap
            assert chips == [0, 1, 2, 3, 0, 1]
            assert sorted(srv.spawned) == [0, 1, 2, 3]
            assert len(srv.workers) == 4
            # a bucket is sticky to its chip
            assert srv._ensure_worker(
                request_key(reqs[2]), worker_tag(reqs[2])).chip == 2
        finally:
            _close(srv)

    @pytest.mark.parametrize("chips", [1, 4])
    def test_worker_pinned_to_its_chip_on_multichip_tpu(
            self, tmp_path, monkeypatch, chips):
        spawned = []

        class FakeProc:
            pid = 1

            def __init__(self, cmd, env):
                spawned.append((cmd, env))

            def poll(self):
                return None

        monkeypatch.setattr(supervisor.subprocess, "Popen", FakeProc)
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
        srv = FrontendServer(port=0, checkpoint_dir=str(tmp_path),
                             devices=(chips, "tpu"))
        try:
            for r in (_req("taylor_green", 100), _req("dam_break", 100)):
                srv._ensure_worker(request_key(r), worker_tag(r))
        finally:
            _close(srv)
        assert len(spawned) == min(chips, 2)
        for chip, (cmd, env) in enumerate(spawned):
            assert cmd[cmd.index("--chip") + 1] == str(chip)
            assert env["JAX_PLATFORMS"] == "tpu"  # no fallback to the CPU
            if chips == 1:  # a one-chip host: nothing to pin
                assert "TPU_VISIBLE_CHIPS" not in env
            else:
                assert {k: env[k] for k in supervisor.pin_env(chip)} == \
                    supervisor.pin_env(chip)


    @pytest.mark.parametrize("platform", ["tpu", "cpu"])
    def test_worker_on_another_platform_is_refused(self, tmp_path,
                                                   platform):
        """A worker whose device is not the probed platform (a TPU it
        could not open, say) dies and is respawned; it never serves."""
        srv = _pool(tmp_path, 1)
        try:
            r = _req("taylor_green", 100)
            h = srv._ensure_worker(request_key(r), worker_tag(r))
            srv._handle_worker_frame(h, {
                "type": "hello", "pid": 1, "platform": platform,
                "kind": f"{platform} device"})
            (w,) = srv._extra_stats()["workers"]
            assert w["platform"] == platform
            if platform == "tpu":
                assert h.state == "ready" and srv.worker_restarts == 0
            else:
                assert h.state == "backoff" and srv.worker_restarts == 1
        finally:
            _close(srv)


class _FakeServer:
    """Scripted frame server: each accepted connection plays the next
    scenario entry — a list of frames to send (after reading the
    request), or the string "eof" to hang up mid-stream."""

    def __init__(self, scenario):
        self.scenario = list(scenario)
        self.requests = []
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        for entry in self.scenario:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            with conn:
                self.requests.append(recv_frame(conn))
                if entry == "eof":
                    continue  # close without a terminal frame
                for frame in entry:
                    send_frame(conn, frame)
        self.sock.close()


class TestResilientClient:
    def test_retry_after_token_resubmitted(self):
        fake = _FakeServer([
            [{"type": "retry_after", "token": "tok-1", "steps_done": 8}],
            [{"type": "obs", "step": 16, "ekin": 1.0},
             {"type": "done", "steps": 16, "obs": {}}],
        ])
        frames, term = client.run_request_resilient(
            "127.0.0.1", fake.port,
            {"case": "taylor_green", "nsteps": 16, "observe": True},
            retries=3, backoff_s=0.01)
        assert term["type"] == "done"
        # the resubmission carried the token, not the original case
        assert fake.requests[1] == {"resume_token": "tok-1",
                                    "observe": True}
        # frames accumulate across attempts
        assert [f["type"] for f in frames] == ["retry_after", "obs",
                                               "done"]

    def test_midstream_eof_reconnects(self):
        fake = _FakeServer([
            "eof",
            [{"type": "done", "steps": 8, "obs": {}}],
        ])
        frames, term = client.run_request_resilient(
            "127.0.0.1", fake.port,
            {"case": "taylor_green", "nsteps": 8},
            retries=2, backoff_s=0.01)
        assert term["type"] == "done"
        assert len(fake.requests) == 2
        # both attempts sent the original request (no token yet)
        assert fake.requests[0] == fake.requests[1]

    def test_retry_budget_exhausted_returns_last_terminal(self):
        fake = _FakeServer([
            [{"type": "retry_after", "token": None}],
            [{"type": "retry_after", "token": None}],
        ])
        _, term = client.run_request_resilient(
            "127.0.0.1", fake.port, {"case": "taylor_green"},
            retries=1, backoff_s=0.01)
        assert term["type"] == "retry_after"
        assert len(fake.requests) == 2  # initial + one retry, then stop

    def test_nonrecoverable_terminal_passes_through(self):
        fake = _FakeServer([
            [{"type": "rejected", "reason": "busy", "queue": 1}],
        ])
        _, term = client.run_request_resilient(
            "127.0.0.1", fake.port, {"case": "taylor_green"},
            retries=3, backoff_s=0.01)
        assert term["type"] == "rejected"
        assert len(fake.requests) == 1  # no retries burned


@pytest.mark.slow
class TestSupervisorE2E:
    def test_sigkill_recovery_bit_identical_sibling_unaffected(
            self, tmp_path):
        """The tentpole proof: SIGKILL one engine worker mid-request
        (the supervisor's deterministic chaos-kill — a real SIGKILL
        timed right after a committed block checkpoint); its request
        must finish bit-identical to an uninterrupted run, a request in
        a DIFFERENT bucket, served by the other device's worker, must
        stream through undisturbed, and the frontend must never exit.
        The host is a two-device CPU (XLA's forced host device count),
        so the pool holds two workers."""
        srv = chaos.ServerProc("--chaos", "kill",
                               checkpoint=str(tmp_path / "ck"),
                               block=BLOCK, devices=2)
        results = {}

        def fire(rid, req):
            frames, term = client.run_request(
                "127.0.0.1", srv.port, req, timeout=600.0)
            results[rid] = (frames, term)

        ta = threading.Thread(target=fire, args=("a", {
            "case": "taylor_green", "n": 1000, "nsteps": 160,
            "observe": True, "return_state": True}))
        ta.start()
        # chaos-kill fires once the victim worker has >= 2 blocks; the
        # sibling starts only after the fire, so it runs exactly while
        # the victim's bucket is dead/restarting
        srv.wait_stats(lambda st: st["chaos_fired"], timeout=300,
                       what="chaos fire")
        assert srv.alive()
        tb = threading.Thread(target=fire, args=("b", {
            "case": "taylor_green", "n": 150, "nsteps": 64,
            "observe": True, "return_state": True}))
        tb.start()
        ta.join(600)
        tb.join(600)
        assert srv.alive(), "frontend died during worker recovery"

        frames_a, term_a = results["a"]
        frames_b, term_b = results["b"]
        assert term_a["type"] == "done" and term_a["steps"] == 160
        assert term_b["type"] == "done" and term_b["steps"] == 64
        # the killed bucket's client saw the recovery event...
        assert any(f.get("action") == "recovering" for f in frames_a)
        # ...the sibling bucket saw a clean, gap-free stream
        assert not any(f.get("action") == "recovering" for f in frames_b)
        obs_b = [f["step"] for f in frames_b if f["type"] == "obs"]
        assert obs_b == list(range(BLOCK, 64, BLOCK))
        # bit-identity for BOTH buckets
        _assert_state_equal(term_a, _solo_state(1000, 160), "killed")
        _assert_state_equal(term_b, _solo_state(150, 64), "sibling")
        # the killed bucket re-covered every block boundary (duplicates
        # around the kill point are allowed; gaps are not)
        obs_a = {f["step"] for f in frames_a if f["type"] == "obs"}
        assert obs_a == set(range(BLOCK, 160, BLOCK))

        st = srv.stats()
        # one worker per device, each bucket on its own
        assert st["chips"] == 2 and len(st["workers"]) == 2
        assert sorted(len(w["buckets"]) for w in st["workers"]) == [1, 1]
        assert st["worker_restarts"] >= 1
        assert st["recovered_lanes"] >= 1
        assert st["recovery_s"] is not None and st["recovery_s"] > 0
        assert srv.stop() == 0
        # quiet reclaim: the restarted worker logged ONE summary line,
        # not a per-lane lockfile warning
        spam = [ln for ln in srv.lines if "checkpoint: reclaiming" in ln]
        assert spam == [], spam
        assert any("reclaimed checkpoint lock(s)" in ln
                   for ln in srv.lines)
        assert any("# drained cleanly" in ln for ln in srv.lines)

    def test_max_restarts_exhaustion_token_resumes(self, tmp_path):
        """--max-restarts 0: the first real SIGKILL sheds the in-flight
        request as RETRY_AFTER with a resume token; resubmitting the
        token (fresh worker, fresh budget) finishes from the checkpoint
        bit-identical to an uninterrupted run."""
        srv = chaos.ServerProc("--max-restarts", "0",
                               checkpoint=str(tmp_path / "ck"),
                               block=BLOCK)
        box = {}

        def fire():
            box["r"] = client.run_request(
                "127.0.0.1", srv.port,
                {"case": "taylor_green", "n": 1000, "nsteps": 160,
                 "return_state": True}, timeout=600.0)

        t = threading.Thread(target=fire)
        t.start()
        # kill by hand (test-driven injection) once a block checkpoint
        # has certainly committed
        st = srv.wait_stats(
            lambda st: any(w["blocks"] >= 2 and w["assigned"]
                           for w in st["workers"]),
            timeout=300, what="2 blocks of progress")
        pids = srv.worker_pids()
        assert pids, st
        chaos.sigkill(next(iter(pids.values())))
        t.join(120)
        _, term = box["r"]
        assert term["type"] == "retry_after", term
        token = term["token"]
        assert token and term["steps_done"] > 0
        assert srv.alive()

        # the resilient client path: resubmit the token to completion
        frames, done = client.run_request_resilient(
            "127.0.0.1", srv.port,
            {"resume_token": token, "return_state": True},
            retries=3, timeout=600.0)
        assert done["type"] == "done" and done["steps"] == 160
        _assert_state_equal(done, _solo_state(1000, 160), "resumed")
        accepted = next(f for f in frames if f["type"] == "accepted")
        assert accepted["resumed"] is True
        assert srv.stop() == 0
