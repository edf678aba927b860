"""chip_smoke.py on the CPU: its legs run at tiny size through the
SAME functions the chip run calls, and the script itself refuses to
pass without an accelerator, without a native build, and when any leg
fails.  Also the compile-cache placement rule (utils/jaxenv)."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run_smoke(env_extra=None, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=300)


# -- the legs, tiny, on the CPU ----------------------------------------------


def test_leg_kernels_tiny_interpret():
    out = chip_smoke.leg_kernels(
        seed=3, expect_platform="cpu",
        shapes=((128, 8), (384, 16), (2048, 8), (4096, 4), (64, 8)),
        snap_hash_bytes=70_001, snap_stream_chunks=3, interpret=True)
    assert out["device"]["platform"] == "cpu"
    # both forms ran on every shape: the rule's entry point and the
    # Pallas kernel called directly (interpret passed HERE, explicitly)
    assert all(forms == ["raw_crc_batch", "raw_crc_pallas"]
               for _w, _n, forms in out["shapes"])


def test_leg_kernels_fails_on_one_wrong_shape(monkeypatch):
    import jax.numpy as jnp

    from etcd_tpu.ops import crc_device

    real = crc_device.raw_crc_batch

    def wrong_at_384(buf, use_pallas=None):
        out = real(buf, use_pallas=use_pallas)
        return out ^ jnp.uint32(1) if buf.shape[1] == 384 else out

    monkeypatch.setattr(crc_device, "raw_crc_batch", wrong_at_384)
    with pytest.raises(chip_smoke.SmokeError, match=r"\[16,384\]"):
        chip_smoke.leg_kernels(
            seed=3, expect_platform="cpu",
            shapes=((128, 8), (384, 16)), snap_hash_bytes=5000,
            snap_stream_chunks=2)


def test_leg_cohosted_tiny(tmp_path):
    out = chip_smoke.leg_cohosted(
        str(tmp_path), seed=5, expect_platform="cpu", g=16, members=3,
        puts=24, tenants=24, clients=3, start_timeout=120.0)
    assert out["device"]["platform"] == "cpu"
    assert out["put"]["acked"] == 24
    assert out["get"]["read"] == 24
    assert out["get_after_restart"]["read"] == 24
    assert out["replay"]["route"] == "stream"
    assert out["replay"]["entries"] > 24


def test_leg_cohosted_rejects_wrong_platform(tmp_path):
    with pytest.raises(chip_smoke.SmokeError, match="platform 'cpu'"):
        chip_smoke.leg_cohosted(
            str(tmp_path), seed=5, expect_platform="tpu", g=4,
            members=3, puts=2, tenants=2, clients=1,
            start_timeout=120.0)


def test_leg_dist_tiny(tmp_path):
    from etcd_tpu.wal.backend_policy import set_policy

    set_policy(None)  # a fresh router: this leg reads its decisions
    try:
        out = chip_smoke.leg_dist(str(tmp_path), seed=7,
                                  expect_platform="cpu", g=8, puts=12)
    finally:
        set_policy(None)
    assert out["put"]["acked"] == 12
    # every key linearizably from each of the three members, twice
    assert out["get"]["read"] == 36
    assert out["get_after_restart"]["read"] == 36
    assert out["replay"]["route"] == "stream"
    assert out["replay"]["why"] == "strict_device"


# -- one process per chip ----------------------------------------------------


def test_smoke_parent_never_imports_jax():
    """The parent spawns the chip-holding children, so it must stay
    off jax itself — through import AND the native rebuild check."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "from etcd_tpu import native; native.available(); "
            "import etcd_tpu.cli; "
            "sys.exit('jax' in sys.modules)" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True,
                   timeout=120)


# -- the script's own exit codes ---------------------------------------------


def test_smoke_exits_nonzero_without_accelerator():
    r = _run_smoke(None, "--legs", "kernels")
    assert r.returncode != 0
    assert "expected 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout  # no result line


def test_smoke_exits_nonzero_when_native_cannot_build():
    # the Makefile's `CXX ?= g++` takes the environment's compiler;
    # a failed build leaves the existing .so alone
    r = _run_smoke({"CXX": "false"}, "--legs", "kernels")
    assert r.returncode != 0
    assert "CalledProcessError" in r.stderr
    assert '"ok"' not in r.stdout


def _fake_leg(count, fail=None):
    def run_leg(leg, workdir, seed):
        if leg == fail:
            raise chip_smoke.SmokeError(f"{leg} failed")
        return {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": count}}
    return run_leg


def test_main_runs_legs_in_order_and_adds_mesh_legs(monkeypatch,
                                                    capsys):
    ran = []
    real = _fake_leg(4)

    def run_leg(leg, workdir, seed):
        ran.append(leg)
        return real(leg, workdir, seed)

    monkeypatch.setattr(chip_smoke, "run_leg", run_leg)
    monkeypatch.setattr(chip_smoke, "rebuild_native", lambda: None)
    assert chip_smoke.main([]) == 0
    assert ran == list(chip_smoke.LEGS)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 4}}')
    # an explicit --legs list is run as given, nothing added
    ran.clear()
    assert chip_smoke.main(["--legs",
                            "kernels,cohosted_mesh,dist_mesh"]) == 0
    assert ran == ["kernels", "cohosted_mesh", "dist_mesh"]
    monkeypatch.setattr(chip_smoke, "run_leg", _fake_leg(1))
    assert chip_smoke.main([]) == 0  # one chip: no mesh legs asked


@pytest.mark.parametrize("leg", chip_smoke.LEGS[:3])
def test_main_fails_when_any_single_leg_fails(monkeypatch, capsys,
                                              leg):
    monkeypatch.setattr(chip_smoke, "run_leg", _fake_leg(1, fail=leg))
    monkeypatch.setattr(chip_smoke, "rebuild_native", lambda: None)
    with pytest.raises(chip_smoke.SmokeError, match=leg):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


# -- compile-cache placement -------------------------------------------------


def _cache_dir_in_child(env):
    code = ("from etcd_tpu.utils.jaxenv import configure_compile_cache"
            " as c; import jax; print(c()); "
            "print(jax.config.jax_compilation_cache_dir); "
            "print(jax.config."
            "jax_persistent_cache_min_compile_time_secs)")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       check=True)
    return r.stdout.split()


def test_cache_dir_env_set_code_sets_nothing(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "given"))
    returned, configured, threshold = _cache_dir_in_child(env)
    assert returned == configured == str(tmp_path / "given")
    assert float(threshold) == 0.0
    assert not os.path.exists(os.path.join(REPO, ".jax_cache",
                                           "given"))


def test_cache_dir_unset_is_checkout_jax_cache():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    returned, configured, threshold = _cache_dir_in_child(env)
    assert returned == configured == os.path.join(REPO, ".jax_cache")
    assert float(threshold) == 0.0


def test_no_other_cache_dir_setter_in_the_tree():
    setter = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir["']"""
        r"""|(environ\[|setdefault\(|putenv\()\s*"""
        r"""["']JAX_COMPILATION_CACHE_DIR["']""")
    paths = [os.path.join(REPO, n) for n in os.listdir(REPO)
             if n.endswith(".py")]
    for top in ("etcd_tpu", "scripts", "tests"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, top)):
            paths += [os.path.join(dirpath, n) for n in files
                      if n.endswith(".py")]
    setters = []
    for path in paths:
        with open(path, errors="replace") as f:
            if setter.search(f.read()):
                setters.append(os.path.relpath(path, REPO))
    assert setters == ["etcd_tpu/utils/jaxenv.py"]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
