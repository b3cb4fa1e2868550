"""``chip_smoke.py`` rehearsed on the CPU at a small size.

The phases run in this process at n = 4096 with the kernels in interpret
mode; the device check of phase 1 is left out of those calls and tested on
its own (it must refuse a CPU).  The script itself runs in child processes:
it must fail without a TPU and without the repo around it.
"""
from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.serve import default_cfg  # noqa: E402

N, NQ = 4096, 64
#: the serving default cut to a CPU test's size: fewer Phi steps and a
#: smaller projection sample
SMALL_CFG = default_cfg("infinity", budget=256, rerank=96, train_steps=100,
                        proj_sample=256)


def _child_env(tmp_path, **extra) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["PYTHONPATH"] = str(ROOT / "src")
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def data():
    X, Q = cs.make_data(N, NQ, seed=0)
    return X, Q, cs.exact_topk(X, Q, cs.K, chunk=1000)


@pytest.fixture(scope="module")
def infinity_server(data):
    X, Q, ref = data
    server = cs.phase_brute(X, Q, ref)
    recalls = cs.phase_infinity(server, X, Q, ref, cfg=SMALL_CFG)
    return server, recalls


def test_reference_is_the_exact_top_k(data):
    X, Q, (ref_d, ref_i) = data
    full = np.sqrt(((X[None].astype(np.float64) - Q[:, None]) ** 2).sum(-1))
    want = np.argsort(full, axis=1, kind="stable")[:, : cs.K]
    np.testing.assert_array_equal(ref_i, want)
    np.testing.assert_allclose(ref_d, np.take_along_axis(full, want, 1),
                               rtol=1e-9)


def test_brute_phase(data):
    X, Q, ref = data
    assert cs.phase_brute(X, Q, ref).engine == "brute"


def test_id_check_rejects_a_wrong_answer(data):
    X, Q, (ref_d, ref_i) = data
    wrong = ref_i.copy()
    wrong[3, 0] = ref_i[3, -1] + 1
    with pytest.raises(AssertionError, match="differ from the reference"):
        cs.check_ids("wrong", X, Q, wrong, ref_d, ref_i)


def test_infinity_phase(infinity_server):
    server, recalls = infinity_server
    assert server.engine == "infinity"
    assert set(recalls) == set(cs.BATCHES)
    for r, hit in recalls.values():
        assert 0.0 <= r <= 1.0
        assert hit >= cs.SELF_FLOOR["beam"]
    stages = server.index.train_history["build_s"]
    assert set(stages) == {"projection", "phi", "embed", "tree", "flatten"}


@pytest.mark.parametrize("floor", ["floors", "self_floors"])
def test_infinity_phase_enforces_its_floor(data, infinity_server, floor):
    X, Q, ref = data
    server, _ = infinity_server
    with pytest.raises(AssertionError, match="below floor"):
        cs.phase_infinity(server, X, Q, ref, cfg=SMALL_CFG,
                          **{floor: {"beam": 1.01, "best_first": 1.01}})


def test_descent_check_rejects_a_wrong_path(data, infinity_server):
    _, Q, _ = data
    server, _ = infinity_server
    index = server.index

    class Shifted:
        """The index with every descent answer moved to the next row."""
        phi_params, Z, tree = index.phi_params, index.Z, index.tree

        @staticmethod
        def search(Q, **kw):
            res = index.search(Q, **kw)
            return res._replace(idx=(res.idx + 1) % index.Z.shape[0])

    cs.check_descent(index, Q[:8])
    with pytest.raises(AssertionError, match="differ from the host recursion"):
        cs.check_descent(Shifted, Q[:8])


def test_runtime_phase(data, infinity_server):
    _, Q, _ = data
    server, _ = infinity_server
    cs.phase_runtime(server, Q)


def test_kernel_phase(data):
    X, Q, ref = data
    cs.phase_kernels(X, Q, ref, seed=0, qpath_n=256, pdist_n=512)


def test_device_check_refuses_a_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="no TPU found"):
        cs.require_tpu()


def test_compile_cache_follows_the_environment(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own say


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.place_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.place_compile_cache() == path  # fixed, not fresh
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_script_fails_without_a_tpu(tmp_path):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=_child_env(tmp_path))
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _child_env(tmp_path)
    env.pop("PYTHONPATH")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_importing_the_package_starts_no_backend(tmp_path):
    code = (
        "import importlib, pkgutil, repro\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    if not m.name.endswith('dryrun'):\n"
        "        importlib.import_module(m.name)\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=_child_env(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]


def test_sharded_phase_on_four_host_devices(tmp_path):
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "import chip_smoke as cs\n"
        "from repro.launch.serve import default_cfg\n"
        "X, Q = cs.make_data(4096, 64, seed=0)\n"
        "cfg = default_cfg('infinity', budget=256, rerank=96,"
        " train_steps=100, proj_sample=256)\n"
        "out = cs.phase_sharded(X, Q, cs.exact_topk(X, Q, cs.K), cfg=cfg)\n"
        "assert set(out) == {1, 4}, out\n"
    )
    env = _child_env(tmp_path,
                     XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code, str(ROOT)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ids equal 1-device brute" in r.stdout
