"""``chip_smoke.py``'s single-chip phases at a tiny size on the CPU, so a
change that breaks the chip smoke fails here first: spike-train agreement,
exact event accounting, and a service whose requests all complete.  The
script itself must refuse to run without a TPU."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

N, T_END = 32, 5.0


@pytest.fixture(scope="module")
def model():
    return cs.soma_model()


@pytest.fixture(scope="module")
def lab():
    return cs.lab_network(N, 0)


@pytest.fixture(scope="module")
def dense(model, lab):
    net, iinj = lab
    return cs.phase_dense(model, net, iinj, T_END)


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_phase_dense_accounts_every_event(dense, lab):
    net, _ = lab
    assert int(dense.rec.count.sum()) > 0
    assert int(dense.n_events) == cs.implied_events(dense, net, T_END) > 0


@pytest.mark.parametrize("batch_cap", [N, 8])
def test_phase_compact_agrees_with_dense(model, lab, dense, batch_cap,
                                         capsys):
    """A cap that covers the network reproduces the dense trains exactly;
    a cap of N/4 rolls work and must stay inside the envelope."""
    net, iinj = lab
    cs.phase_compact(model, net, iinj, T_END, dense, batch_cap=batch_cap)
    out = capsys.readouterr().out
    assert '"phase": "compact"' in out
    assert ('"rolled": true' in out) == (batch_cap < N)


def test_phase_anchor_on_cpu(model, capsys):
    ag = cs.phase_anchor(model, T_END, 0, n=N)
    assert ag["unpaired"] == 0 and ag["max_dt_ms"] == 0.0   # CPU vs CPU
    out = capsys.readouterr().out
    assert '"compact": {"batch_cap": 32' in out
    assert '"vs_dense": {"paired"' in out


def test_phase_service_completes(monkeypatch, tmp_path, capsys):
    # with the variable set, ``serve.main`` places no cache of its own
    # (JAX read the variable at import, before it was set here)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cs.phase_service(n=16, tenants=3, lanes=2, t_end=3.0)
    assert '"completed": 3' in capsys.readouterr().out


def test_agreement_checks_reject_divergent_trains(dense):
    """The comparison itself: a shifted spike is caught by the identity
    check and passes the envelope; unpaired spikes beyond the envelope's
    allowance are caught by both."""
    ag = cs.agreement(dense, dense)
    cs.check_agreement(ag, True, "self")
    rec = dense.rec
    i = int(np.flatnonzero(np.asarray(rec.count))[0])
    times = np.asarray(rec.times).copy()
    times[i, 0] += 0.1
    shifted = dense._replace(rec=rec._replace(times=times))
    ag = cs.agreement(dense, shifted)
    assert ag["max_dt_ms"] == pytest.approx(0.1)
    with pytest.raises(AssertionError):
        cs.check_agreement(ag, True, "shifted")
    cs.check_agreement(ag, False, "shifted")
    count = np.asarray(rec.count).copy()
    times = np.asarray(rec.times).copy()
    silent = np.flatnonzero(count == 0)[:3]
    times[silent, 0], count[silent] = 0.5, 1
    extra = dense._replace(rec=rec._replace(times=times, count=count))
    ag = cs.agreement(dense, extra)
    assert ag["unpaired"] == 3 and ag["first_unpaired_ms"] == 0.5
    for same in (True, False):
        with pytest.raises(AssertionError):
            cs.check_agreement(ag, same, "extra")


SPMD_SCRIPT = """
import json, sys
import jax
sys.path.insert(0, {root!r})
import chip_smoke as cs
model = cs.soma_model()
net, iinj = cs.lab_network(256, 0)
cs.phase_spmd(model, net, iinj, 5.0, jax.devices(), spike_cap=64)
"""


def test_phase_spmd_on_four_host_devices(tmp_path):
    """The ``--chips 4`` phase on 4 host-platform devices (a subprocess:
    the device count is fixed when JAX starts): both rounds precompiled
    into the persistent cache, then sparse_ragged equal to allgather."""
    import json
    import subprocess
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    res = subprocess.run([sys.executable, "-c", SPMD_SCRIPT.format(root=ROOT)],
                         env=env, capture_output=True, text=True, timeout=600,
                         cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    phases = {p["phase"]: p for p in (
        json.loads(line[len("phase "):]) for line in res.stdout.splitlines()
        if line.startswith("phase "))}
    assert set(phases) == {"spmd_precompile", "spmd_allgather",
                           "spmd_sparse_ragged", "spmd_compare"}
    assert phases["spmd_compare"]["paired"] > 0
    assert phases["spmd_compare"]["unpaired"] == 0
    assert any(tmp_path.iterdir())       # the rounds went to the cache


def test_compile_cache_placement(monkeypatch, restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing; without
    it the cache goes to <repo>/.jax_cache."""
    from repro.launch import compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir is None
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
