"""chip_smoke.py's phases on the CPU at smoke size (kernels in interpret
mode), its refusals without a TPU, and the compile-cache placement it and
the other entry points share."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch import compile_cache
from repro.models import transformer as T

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod       # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def small_traffic(smoke):
    return smoke.Traffic(n_requests=4, shared_prefix=16, suffix=(9, 24),
                         gen=4, slots=2, pages=64, page_size=8, max_len=64,
                         ref_len=16)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_one_chip_phase_on_cpu(smoke, kv_dtype):
    """The default phase end to end: every request served in full, prefix
    hits, prefill and decode logits within the script's own tolerance of
    the XLA/unfused reference."""
    cfg = get_smoke_config("qwen2-1.5b", vocab=256)
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    traffic = small_traffic(smoke)
    out = smoke.one_chip_phase(cfg, params, traffic, 0, kv_dtype=kv_dtype)
    stats = out["stats"]
    assert stats["prefix_hits"] > 0
    assert stats["live_requests"] == 0 and stats["waiting_requests"] == 0
    assert set(out["logits"]) == {"prefill", "decode"}
    for step in out["logits"].values():
        assert step["rel_l2"] <= smoke.LOGIT_RTOL[kv_dtype]


def test_tp_phase_on_four_cpu_devices(smoke):
    """The --tp phase on four forced host devices (a child process: this
    one keeps the real one-device CPU platform): sharded init, TP serving
    with the KV pool split four ways under the registry config's own
    sharding rules, TP=4 vs one-device prefill and decode logits."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke as S
        from repro.configs.registry import get_smoke_config
        cfg = get_smoke_config("qwen3-8b", n_layers=2, vocab=256,
                               n_heads=8, n_kv_heads=4)
        t = S.Traffic(n_requests=4, shared_prefix=16, suffix=(9, 24), gen=4,
                      slots=2, pages=64, page_size=8, max_len=64, ref_len=16)
        out = S.tp_phase(cfg, 4, t, 0)
        print("TP-SMOKE", max(c["rel_l2"] for c in out["logits"].values()),
              out["stats"]["prefix_hits"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    line = next(x for x in r.stdout.splitlines() if x.startswith("TP-SMOKE"))
    rel, hits = line.split()[1:]
    assert float(rel) <= smoke.LOGIT_RTOL[None] and int(hits) > 0


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "'cpu'" in err


def test_script_alone_fails(tmp_path):
    """Without the rest of the repository the script exits non-zero and
    prints no result line."""
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, SCRIPT.name], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set and is left to JAX;
    otherwise the cache goes to the checkout's fixed .jax_cache/."""
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert compile_cache.use_compile_cache() == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(
                ROOT / ".jax_cache")
        else:
            where = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", where)
            assert compile_cache.use_compile_cache() == where
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
