"""chip_smoke.py off the chip: every phase function at a tiny size on
the CPU, the refusals (no TPU, a phase that raises), and the one place
that sets the compile cache."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(lanes=32, members=5, conns=64, waves=3, wave_ops=200,
            chaos_lanes=3, socket_ops=3, ring_capacity=64, cmds=8,
            superstep_k=2)


def test_phase_device_names_what_it_stands_on(tmp_path):
    out = chip_smoke.phase_device(str(tmp_path / "wal"), "somewhere")
    assert out["platform"] == jax.devices()[0].platform
    assert out["count"] == len(jax.devices())
    assert out["wal_io_path"] in ("native", "python")
    assert out["wal_fs"] != "" and out["compile_cache_dir"] == "somewhere"
    assert out["jax"] == jax.__version__


def test_phase_served_path_tiny(tmp_path):
    out = chip_smoke.phase_served_path(
        str(tmp_path / "wal"), wal_shards=2, reopen_wal_shards=3, **TINY)
    assert out["ring_io"] == "gather"      # what "auto" means off a TPU
    # counted when the serving window closes: warm-up + waves, fleet
    # and socket client together
    assert out["ops"] == TINY["wave_ops"] + TINY["waves"] * (
        TINY["wave_ops"] + TINY["socket_ops"])
    assert out["wal_io_path"] in ("native", "python")
    assert out["phase_p50_ms"]["device_dispatch"] > 0


def test_phase_mesh4_tiny_and_skip(tmp_path):
    out = chip_smoke.phase_mesh4(str(tmp_path / "wal"), devices=4, **TINY)
    assert out["mesh"] == "1x4" and out["wal_shards"] == 4
    with pytest.raises(chip_smoke.Skip, match="8 devices"):
        chip_smoke.phase_mesh4(str(tmp_path / "w2"), devices=16, **TINY)


def test_phase_reads_exact_tiny():
    out = chip_smoke.phase_reads_exact(lanes=16, members=5, n_keys=8,
                                       cmds=8)
    assert out["values_checked"] == 128 and out["stale_refusals"] >= 1


def test_main_refuses_a_cpu_backend(capsys):
    assert chip_smoke.main() == 2
    cap = capsys.readouterr()
    assert cap.out == ""                   # no result line, no number
    assert "'cpu'" in cap.err


def test_a_phase_that_raises_fails_the_run(monkeypatch, tmp_path, capsys):
    import ra_tpu.utils

    def boom(*_a, **_kw):
        raise RuntimeError("injected")

    def one_device(*_a, **_kw):
        raise chip_smoke.Skip("1 device")

    ran = []
    monkeypatch.setattr(chip_smoke, "_device_stamp", lambda: {
        "platform": "tpu", "kind": "fake", "count": 1})
    monkeypatch.setattr(ra_tpu.utils, "enable_compile_cache",
                        lambda: "nowhere")
    monkeypatch.setattr(chip_smoke, "WAL_ROOT", str(tmp_path / "wal"))
    monkeypatch.setattr(chip_smoke, "phase_served_path", boom)
    monkeypatch.setattr(chip_smoke, "phase_mesh4", one_device)
    monkeypatch.setattr(chip_smoke, "phase_reads_exact",
                        lambda *a, **kw: ran.append("phase_reads_exact"))
    assert chip_smoke.main() == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert any(ln.startswith("phase served_path: FAIL") for ln in lines)
    # the phases after the failure still ran and reported
    assert ran == ["phase_reads_exact"]
    assert any(ln.startswith("phase mesh4: skipped (1 device)")
               for ln in lines)
    assert not lines[-1].startswith("{")   # no result line on failure


def _python(code: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout():
    r = _python(
        "import os, jax\n"
        "from ra_tpu.utils import enable_compile_cache\n"
        "os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)\n"
        "fixed = enable_compile_cache()\n"
        "assert fixed == os.path.join(os.getcwd(), '.jax_cache'), fixed\n"
        "assert jax.config.jax_compilation_cache_dir == fixed\n"
        "os.environ['JAX_COMPILATION_CACHE_DIR'] = '/placed/outside'\n"
        "assert enable_compile_cache() == '/placed/outside'\n"
        "# set from outside: the helper sets nothing\n"
        "assert jax.config.jax_compilation_cache_dir == fixed\n")
    assert r.returncode == 0, r.stderr


def test_one_place_sets_the_compile_cache_directory():
    hits = []
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "__pycache__"]
        for name in names:
            path = os.path.join(root, name)
            if name.endswith(".py") and path != os.path.abspath(__file__):
                with open(path, encoding="utf-8") as f:
                    if "compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("ra_tpu", "utils", "__init__.py")]
