"""Fault-tolerance runtime logic."""
import os
import time

from repro.runtime.fault_tolerance import (
    HeartbeatMonitor, HeartbeatWriter, StragglerWatchdog,
    plan_elastic_mesh)


def test_heartbeat_roundtrip(tmp_path):
    w0 = HeartbeatWriter(str(tmp_path), 0)
    w1 = HeartbeatWriter(str(tmp_path), 1)
    w0.beat(5)
    w1.beat(5)
    mon = HeartbeatMonitor(str(tmp_path), timeout_s=60)
    assert sorted(mon.alive_hosts()) == [0, 1]
    assert mon.dead_hosts(expected=3) == [2]


def test_heartbeat_timeout(tmp_path):
    w = HeartbeatWriter(str(tmp_path), 0)
    w.beat(1)
    mon = HeartbeatMonitor(str(tmp_path), timeout_s=0.05, skew_s=0.0)
    time.sleep(0.1)
    assert mon.dead_hosts(expected=1) == [0]


def test_heartbeat_clear_removes_file(tmp_path):
    """Clean shutdown removes the heartbeat (and any torn .tmp), so a
    later resume reads "absent" instead of mistaking the clean exit
    for a dead process. clear() is idempotent."""
    w = HeartbeatWriter(str(tmp_path), 0)
    w.beat(7)
    with open(w.path + ".tmp", "w") as f:
        f.write("{")  # a torn in-flight write the crash left behind
    w.clear()
    assert not os.path.exists(w.path)
    assert not os.path.exists(w.path + ".tmp")
    w.clear()  # idempotent: nothing to remove is not an error


def test_host_status_tristate(tmp_path):
    mon = HeartbeatMonitor(str(tmp_path), timeout_s=60)
    # never started
    assert mon.host_status(0) == "absent"
    # fresh beat
    w = HeartbeatWriter(str(tmp_path), 0)
    w.beat(1)
    assert mon.host_status(0) == "alive"
    # stale beat: the process stopped beating without clear()
    stale = HeartbeatMonitor(str(tmp_path), timeout_s=0.01, skew_s=0.0)
    time.sleep(0.05)
    assert stale.host_status(0) == "dead"
    # clean shutdown: back to absent, NOT dead
    w.clear()
    assert stale.host_status(0) == "absent"
    # corrupt file (killed mid-write after replace): counts as dead
    with open(w.path, "w") as f:
        f.write("{not json")
    assert mon.host_status(0) == "dead"


def test_heartbeat_staleness_ignores_forged_wall_time(tmp_path):
    """Liveness is judged by the heartbeat file's mtime, NOT the wall
    time recorded inside it: an NTP step or suspend/resume that shifts
    the writer's clock must not flip a beating host dead (or keep a
    dead one alive)."""
    import json

    w = HeartbeatWriter(str(tmp_path), 0)
    w.beat(3)
    with open(w.path) as f:
        rec = json.load(f)
    # forge `t` an hour in the past (writer clock stepped backward);
    # the file itself is fresh on disk -> still alive
    rec["t"] -= 3600.0
    with open(w.path, "w") as f:
        json.dump(rec, f)
    mon = HeartbeatMonitor(str(tmp_path), timeout_s=60)
    assert mon.host_status(0) == "alive"
    # the recorded wall time survives as a diagnostic in the record
    assert mon.alive_hosts()[0]["t"] == rec["t"]
    # forge `t` an hour in the FUTURE but age the file on disk past
    # timeout+skew -> dead, regardless of the optimistic record
    rec["t"] = time.time() + 3600.0
    with open(w.path, "w") as f:
        json.dump(rec, f)
    old = time.time() - 100.0
    os.utime(w.path, (old, old))
    stale = HeartbeatMonitor(str(tmp_path), timeout_s=60, skew_s=2.0)
    assert stale.host_status(0) == "dead"
    assert 0 not in stale.alive_hosts()


def test_heartbeat_skew_allowance(tmp_path):
    """skew_s widens the mtime staleness window (coarse-mtime or NFS
    filesystems); zero skew is the strict wall-clock-free check."""
    w = HeartbeatWriter(str(tmp_path), 0)
    w.beat(1)
    old = time.time() - 5.0
    os.utime(w.path, (old, old))
    lax = HeartbeatMonitor(str(tmp_path), timeout_s=4.0, skew_s=2.0)
    strict = HeartbeatMonitor(str(tmp_path), timeout_s=4.0, skew_s=0.0)
    assert lax.host_status(0) == "alive"
    assert strict.host_status(0) == "dead"


def test_straggler_watchdog():
    wd = StragglerWatchdog(threshold=2.0, patience=2)
    for _ in range(10):
        assert not wd.observe(1.0)
    assert wd.observe(5.0)  # straggler event
    assert not wd.flagged  # needs `patience` consecutive
    assert wd.observe(5.0)
    assert wd.flagged
    # baseline not poisoned by slow steps
    assert wd.ema < 1.5


def test_plan_elastic_mesh():
    full = plan_elastic_mesh(256, model_parallel=16, global_batch=256)
    assert full["mesh_shape"] == (16, 16)
    assert full["drop_devices"] == 0
    # lose a host (8 chips): 248 available -> data axis shrinks
    sm = plan_elastic_mesh(248, model_parallel=16, global_batch=256)
    data = sm["mesh_shape"][0]
    assert data * 16 <= 248
    assert 256 % data == 0


# --------------------------------------------------------------------------
# Persistent compilation cache location (runtime/compile_cache.py)
# --------------------------------------------------------------------------
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_PROBE = (
    "import jax, os\n"
    "from repro.runtime import compile_cache\n"
    "d = compile_cache.enable()\n"
    "print(d, jax.config.jax_compilation_cache_dir,"
    " os.environ['JAX_COMPILATION_CACHE_DIR'])\n"
)


def _cache_probe(cwd, env):
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.split()


def test_compile_cache_honours_env_var(tmp_path):
    from repro.runtime import compile_cache

    mine = str(tmp_path / "elsewhere")
    assert compile_cache.cache_dir({"JAX_COMPILATION_CACHE_DIR": mine}) \
        == mine
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=mine)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    # the helper picks nothing of its own: every view is the env's dir
    assert _cache_probe(str(tmp_path), env) == [mine] * 3


def test_compile_cache_fixed_checkout_path(tmp_path):
    from repro.runtime import compile_cache

    want = os.path.join(_ROOT, ".jax_cache")
    assert os.path.realpath(compile_cache.cache_dir({})) == \
        os.path.realpath(want)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    # two processes (new pid, later clock), two working directories:
    # one directory, the checkout's
    runs = [_cache_probe(str(tmp_path), env), _cache_probe("/", env)]
    for run in runs:
        assert [os.path.realpath(p) for p in run] == \
            [os.path.realpath(want)] * 3

