"""The on-chip smoke's body, tiny, on the CPU mesh — and its set-up helpers.

``chip_smoke.py`` itself only runs on a TPU; what can be checked here is
that its importable body holds together (every stage, every check, the
8-device placement and collective checks, the kernels interpreted under
the forced ``pallas`` backend), that the script refuses a non-TPU
platform, that the compile cache lands where it should, and that the
native loader keys its rebuild on source content.
"""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from fps_tpu import ops  # noqa: E402
from fps_tpu.utils import hostenv  # noqa: E402

TINY = chip_smoke.Sizes(
    mf_scale="100k", mf_rank=4, mf_local_batch=1024,
    # 128 x 64 = DIM1_MIN_BATCH ids per worker step: the dim-1 route's floor.
    pa_features=2048, pa_nnz=64, pa_examples=4096, pa_head=256,
    pa_local_batch=128,
    kernel_cases=(
        ("scatter_add_dim1_pallas", 1000, 1, 8192),
        ("gather_rows_dim1_pallas", 1000, 1, 8192),
    ),
)


@pytest.fixture
def pallas_backend():
    prev = ops.get_backend()
    ops.set_backend("pallas")
    yield
    ops.set_backend(prev)


def test_body_runs_tiny_on_8_device_mesh(devices8, pallas_backend):
    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(devices=devices8[:8])
    stages = chip_smoke.run_smoke(mesh, TINY)
    assert {k: v["status"] for k, v in stages.items()} == {
        "kernels": "passed", "mf": "passed", "pa": "passed"}
    # The multi-device checks really ran: tables over all 8 devices, a
    # collective in the MF epoch program, kernels traced interpreted.
    mf = stages["mf"]
    assert mf["placement"]["item_factors"] == {
        "devices": 8, "row_slices": 8,
        "rows_per_shard": mf["placement"]["item_factors"]["rows_per_shard"]}
    assert mf["collectives"]
    assert mf["train_rmse"][1] < mf["train_rmse"][0]
    traced = stages["pa"]["kernels_traced"]
    assert traced and all(k[1] is True for k in traced)


def test_pa_stage_one_device_takes_head_prefix_kernels(devices8,
                                                       pallas_backend):
    """One device is where head-prefix routing applies: the stage itself
    requires head-slice AND full-table dim-1 kernels to have been traced."""
    import dataclasses

    from fps_tpu.parallel.mesh import make_ps_mesh

    mesh = make_ps_mesh(devices=devices8[:1])
    # q * local_batch must reach the 2048-id head-prefix floor.
    sizes = dataclasses.replace(TINY, pa_local_batch=512, pa_nnz=32,
                                pa_examples=2048)
    out = chip_smoke.stage_pa(mesh, sizes)
    assert out["head_prefix_cols"] * 512 >= 2048
    heads = {k[0] for k in out["kernels_traced"] if k[2] == sizes.pa_head}
    assert heads == {"gather.dim1_head", "scatter_add.dim1_head"}


def test_pa_stage_fails_when_no_kernel_is_traced(devices8):
    """Under CPU ``auto`` the route is pure XLA: the stage must say so, not
    pass on route equality."""
    from fps_tpu.parallel.mesh import make_ps_mesh

    assert ops.get_backend() == "auto"
    with pytest.raises(chip_smoke.SmokeFailure, match="no Pallas kernel"):
        chip_smoke.stage_pa(make_ps_mesh(devices=devices8[:1]), TINY)


def test_script_refuses_cpu_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "not 'tpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result line


@pytest.fixture
def cache_key_config():
    """The two settings that key the cache on the programs' metadata,
    restored after a test that lets the helper set them."""
    import jax

    names = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = {n: getattr(jax.config, n) for n in names}
    yield names
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_helper_leaves_env_placed_cache_alone(monkeypatch, tmp_path,
                                                    cache_key_config):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert hostenv.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_key_holds_scopes_and_not_the_checkouts_path(
        monkeypatch, tmp_path, cache_key_config):
    """Wherever the cache is on, its key takes the programs' metadata in
    (the named scopes a trace is read by live there: a tree with new
    scopes must not load an older tree's executables), with the
    checkout's root cut from the source locations."""
    import re

    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    hostenv.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    cut = jax.config.jax_hlo_source_file_canonicalization_regex
    here = os.path.join(ROOT, "fps_tpu", "core", "driver.py")
    assert re.sub(cut, "", here) == os.path.join("fps_tpu", "core",
                                                 "driver.py")
    assert re.sub(cut, "", "/elsewhere" + here) == "/elsewhere" + here


def test_cache_helper_default_is_fixed_in_checkout(monkeypatch, tmp_path,
                                                   cache_key_config):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    # On the CPU backend (this suite's) the cache stays off, and nothing
    # about its key is set.
    assert hostenv.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        first = hostenv.enable_compilation_cache()
        monkeypatch.chdir(tmp_path)
        assert hostenv.enable_compilation_cache() == first
        assert first == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def test_last_stdout_line_is_the_verdict_and_nothing_else(monkeypatch,
                                                          capsys):
    """The driver parses the LAST stdout line: exactly ``ok`` and
    ``device`` {platform, kind, count}. The report is the line before."""
    import json

    ident = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "jax": "0", "jaxlib": "0", "libtpu": "0"}
    monkeypatch.setattr(chip_smoke, "device_identity", lambda: ident)
    monkeypatch.setattr(chip_smoke, "run_smoke",
                        lambda mesh, sizes: {"mf": {"status": "passed"}})
    monkeypatch.setattr(hostenv, "enable_compilation_cache", lambda: None)
    assert chip_smoke.main() == 0
    report, verdict = map(json.loads, capsys.readouterr().out.splitlines())
    assert verdict == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert type(verdict["device"]["count"]) is int
    assert report["report"]["stages"] == {"mf": {"status": "passed"}}


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ compiler")
def test_native_rebuild_keys_on_source_content(monkeypatch, tmp_path):
    from fps_tpu import native

    src = tmp_path / "lib.cc"
    lib = tmp_path / "lib.so"
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB", str(lib))
    src.write_text('extern "C" int f() { return 1; }\n')
    assert native._stale()  # nothing built yet
    assert native._build() and not native._stale()

    # New content under the OLD mtime, library still newer than the
    # source: exactly what an mtime compare cannot see.
    st = os.stat(src)
    src.write_text('extern "C" int f() { return 2; }\n')
    os.utime(src, ns=(st.st_atime_ns, st.st_mtime_ns))
    assert os.path.getmtime(lib) >= os.path.getmtime(src)
    assert native._stale()
    assert native._build() and not native._stale()

    # A library that rode along without its digest is not trusted.
    os.remove(str(lib) + ".sha256")
    assert native._stale()
