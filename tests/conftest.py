import faulthandler
import os
import signal
import subprocess
import sys
import threading

# By default tests see the 1-device CPU backend (the dry-run alone uses 512
# placeholder devices, in its own process).  The multi-device tier-1 job
# exports REPRO_CPU_DEVICES=8 so the whole suite — including the sharded
# score-store parity tests gated on the ``cpu_mesh8`` fixture — runs on an
# 8-device CPU mesh.  This must happen at conftest import time, before any
# test module initializes a jax backend; forcing it any later is a no-op,
# which is why ``run_multidevice`` below exists for the 1-device runs.
_FORCED_DEVICES = os.environ.get("REPRO_CPU_DEVICES")
if _FORCED_DEVICES:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count"
            f"={_FORCED_DEVICES}").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks pkg

import pytest  # noqa: E402

# Every test's wall-clock limit.  The subprocess harnesses below stop
# their children well before it (``_SUBPROCESS_TIMEOUT_S``), so a stuck
# child fails with its own output first.
_TEST_LIMIT_S = 300
_SUBPROCESS_TIMEOUT_S = 240


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Fail a test that runs past ``_TEST_LIMIT_S``.

    At the limit SIGALRM dumps every thread's stack with ``faulthandler``
    into the test's stderr and fails the test, naming the limit.  Tests
    run in the main thread (also in pytest-xdist workers), where SIGALRM
    is delivered.  Where a SIGALRM handler is already installed, the test
    gets only a ``faulthandler`` watchdog dump at the limit.  The
    watchdog also backs up the alarm: a thread stuck in native code runs
    no Python handler until it returns.
    """
    own_alarm = (threading.current_thread() is threading.main_thread()
                 and signal.getsignal(signal.SIGALRM) in (
                     signal.SIG_DFL, signal.SIG_IGN, None))
    faulthandler.dump_traceback_later(_TEST_LIMIT_S + (60 if own_alarm else 0))
    if own_alarm:
        def on_alarm(signum, frame):
            faulthandler.dump_traceback(all_threads=True)
            pytest.fail(f"{item.nodeid} ran past the {_TEST_LIMIT_S} s "
                        "per-test limit (tests/conftest.py)", pytrace=False)
        previous = signal.signal(signal.SIGALRM, on_alarm)
        signal.setitimer(signal.ITIMER_REAL, _TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        if own_alarm:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


def _timed_out(what: str, timeout: float, outputs) -> None:
    """Fail with each child's output up to its ``timeout``."""
    text = "\n".join(f"--- {name} ---\n{out}" for name, out in outputs)
    pytest.fail(f"{what} ran past its {timeout} s timeout\n{text}",
                pytrace=False)


def _text(out) -> str:
    # TimeoutExpired carries bytes even when the run asked for text
    if isinstance(out, bytes):
        return out.decode(errors="replace")
    return out or ""


@pytest.fixture(scope="session")
def cpu_mesh8():
    """An 8-device ("data",) CPU mesh — the sharded-score-store harness.

    Skips when the backend has fewer than 8 devices: run the suite with
    ``REPRO_CPU_DEVICES=8`` (the CI multi-device job does) to exercise
    these tests in-process; the always-on subprocess parity tests cover
    the same paths in plain 1-device runs.
    """
    import jax
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices — run with REPRO_CPU_DEVICES=8")
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def run_multidevice(code: str, n_devices: int = 8,
                    timeout: float = _SUBPROCESS_TIMEOUT_S
                    ) -> "subprocess.CompletedProcess":
    """Run a python snippet on ``n_devices`` forced CPU devices.

    Subprocess-safe: the parent process' jax backend is typically already
    initialized with one device and XLA_FLAGS can no longer change it, so
    the snippet gets a fresh interpreter with the flag exported before any
    jax import.  The snippet must print ``OK`` on success.  Past
    ``timeout`` the snippet is killed and the test fails with its output.
    """
    import re
    env = dict(os.environ)
    # authoritative: strip any inherited device-count flag (the
    # multi-device job exports one via REPRO_CPU_DEVICES) so the snippet
    # runs at exactly the requested count
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count"
        f"={n_devices}").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONUNBUFFERED"] = "1"      # a killed child keeps its output
    try:
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=timeout, env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."))
    except subprocess.TimeoutExpired as e:
        _timed_out("run_multidevice snippet", timeout,
                   [("stdout", _text(e.stdout)), ("stderr", _text(e.stderr))])
    assert "OK" in r.stdout, r.stdout + "\n" + r.stderr
    return r


def run_cluster(code: str, n_procs: int = 2, n_devices_per_proc: int = 4,
                timeout: float = _SUBPROCESS_TIMEOUT_S, extra_env=None
                ) -> list:
    """Run a python snippet on a local ``jax.distributed`` CPU cluster.

    Extends ``run_multidevice`` to real multi-PROCESS topology: ``n_procs``
    fresh interpreters each with ``n_devices_per_proc`` forced CPU devices,
    joined through a coordinator on a free localhost port —
    ``jax.process_count() == n_procs`` and the KV-store host collectives
    (``distributed.hostcomm``) are live.  The snippet runs after
    ``jax.distributed.initialize`` on every process and must print ``OK``
    on each.  Returns the per-process stdouts (process order) so callers
    can compare cross-topology digests.  Past ``timeout`` every process is
    killed and the test fails with each one's output so far.
    """
    import socket
    import textwrap
    import time
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    preamble = textwrap.dedent(f"""
        import os, sys
        sys.path.insert(0, "src"); sys.path.insert(0, "tests")
        import jax
        jax.distributed.initialize(
            coordinator_address="127.0.0.1:{port}",
            num_processes={n_procs},
            process_id=int(os.environ["REPRO_PROC_ID"]))
    """)
    procs = []
    for p in range(n_procs):
        env = dict(os.environ)
        env.pop("REPRO_CPU_DEVICES", None)
        import re
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count"
            f"={n_devices_per_proc}").strip()
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONUNBUFFERED"] = "1"
        env["REPRO_PROC_ID"] = str(p)
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", preamble + code],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=os.path.join(os.path.dirname(__file__), "..")))
    deadline = time.monotonic() + timeout
    outs = []
    for proc in procs:
        try:
            out, _ = proc.communicate(
                timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            rest = [q.communicate()[0] for q in procs[len(outs):]]
            _timed_out(f"{n_procs}-process cluster", timeout,
                       [(f"process {p}", _text(o))
                        for p, o in enumerate(outs + rest)])
        outs.append(out)
    for p, out in enumerate(outs):
        assert "OK" in out, f"--- process {p} ---\n" + out
    return outs


def smoke_engine_setup(freq=None, cadence=None, n=128, meta_batch=16,
                       minibatch=4, fused=True, lr=1e-3):
    """Shared smoke-scale ESEngine fixture for the step parity suites
    (tests/test_frequency.py and tests/test_engine.py build the same
    model/data/engine; keep it in one place so the suites cannot drift).

    Returns (engine, init TrainState, list of meta-batches).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_smoke_config
    from repro.core.engine import ESConfig, ESEngine, init_train_state
    from repro.data.synthetic import SyntheticConfig, SyntheticLM
    from repro.models.layers import ShardCtx
    from repro.optim.adamw import OptConfig

    model_cfg = get_smoke_config("qwen1.5-0.5b")
    ds = SyntheticLM(SyntheticConfig(n_samples=n, seq_len=32,
                                     vocab_size=64, seed=0))
    es_cfg = ESConfig(method="es", minibatch=minibatch, n_train=n,
                      seq_chunk=0, fused_scores=fused)
    opt_cfg = OptConfig(kind="adamw", lr=lr)
    eng = ESEngine(model_cfg, es_cfg, opt_cfg,
                   lambda s: jnp.asarray(1.0, jnp.float32), ShardCtx(),
                   freq=freq, cadence=cadence)
    state = init_train_state(model_cfg, es_cfg, opt_cfg,
                             jax.random.PRNGKey(0), meta_batch)
    batches = [{k: jnp.asarray(v) for k, v in
                ds.batch(np.arange(i * meta_batch,
                                   (i + 1) * meta_batch)).items()}
               for i in range(n // meta_batch)]
    return eng, state, batches


def assert_trees_equal(a, b):
    """Leaf-wise exact array equality over two pytrees."""
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
