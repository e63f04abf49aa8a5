"""The harness on several chips, and a model family added by files alone.

Cases that need four devices run in a child process
(``bench/tests/multichip.py``) whose XLA_FLAGS give the CPU four devices
before JAX starts; this process keeps its one."""
import hashlib
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench.harness import run_cell
from bench.model import init_weights, load_family, program_config
from bench.peaks import PEAKS
from bench.tests.smoke import REPO, SEED, SMOKE_CONFIG, make_root

FOUR = dict(SMOKE_CONFIG["serving"], chips=4)

# SMOKE_CONFIG's weights from SEED, as the weights of qwen2 configurations
# were made before the tree was read from the program: any change to the
# leaves, their order, their kinds or their fold_in indices shows here
SMOKE_WEIGHTS_SHA256 = (
    "3657069929ad8a86d2d826e23fba3c24bc8da85fad06560bff5ac50ca6d3c643")

# A model type the benchmark does not ship, added as a later change adds
# one: a configuration file, a family file (which reads one published key)
# and a reference file. Its layer is Qwen3's: q and k RMS-normed per head
# before RoPE, no q/k/v bias. Its 4 KV heads divide four chips, so the pool
# and attention are sharded.
QKNORM_CONFIG = dict(
    SMOKE_CONFIG, name="smoke-qknorm", model_type="smoke_qknorm",
    num_attention_heads=8, num_key_value_heads=4, attention_bias=False,
    serving=FOUR)
QKNORM_FAMILY = '''PROGRAM = {"family": "dense", "qk_norm": True}


def config(raw):
    return {"qkv_bias": raw["attention_bias"]}
'''
QKNORM_REFERENCE = '''"""GQA decoder with q and k RMS-normed per head, no q/k/v bias."""
import jax
import jax.numpy as jnp

from bench.reference.qwen2 import _attention, _rms, _rope
from bench.reference.qwen2 import logits  # noqa: F401 (the reference's)


def _layer(h, lw, pos, *, H, Hkv, dh, theta):
    lw = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), lw)
    a, S = lw["attn"], h.shape[0]
    x = _rms(h, lw["attn_norm"]["scale"])
    q = _rms((x @ a["wq"]).reshape(S, H, dh), a["q_norm"]["scale"])
    k = _rms((x @ a["wk"]).reshape(S, Hkv, dh), a["k_norm"]["scale"])
    v = (x @ a["wv"]).reshape(S, Hkv, dh)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    h = h + _attention(q, k, v).reshape(S, H * dh) @ a["wo"]
    x = _rms(h, lw["mlp_norm"]["scale"])
    gate, up = jnp.split(x @ lw["mlp"]["wi"], 2, axis=-1)
    return h + (jax.nn.silu(gate) * up) @ lw["mlp"]["wo"]


def hidden(w, raw, tokens):
    H, Hkv = raw["num_attention_heads"], raw["num_key_value_heads"]
    pos = jnp.arange(tokens.shape[0])

    def layer(c, lw):
        return _layer(c, lw, pos, H=H, Hkv=Hkv, dh=raw["head_dim"],
                      theta=float(raw["rope_theta"])), None

    with jax.default_matmul_precision("highest"):
        h = w["embed"][tokens].astype(jnp.float32)
        h, _ = jax.lax.scan(layer, h, w["layers"])
        return _rms(h, w["final_norm"]["scale"].astype(jnp.float32))
'''


def child(*args) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.multichip",
                        *map(str, args)], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_weights_over_four_devices_equal_one_bitwise():
    out = child("weights")
    assert out["devices"] == 4 and out["sharded_leaves"] > 0
    assert out["equal"]


def test_smoke_weights_checksum():
    fam = load_family(REPO, SMOKE_CONFIG)
    w = init_weights(program_config(SMOKE_CONFIG, fam), fam, SEED)
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(w):
        h.update(np.asarray(x).view(np.uint16).tobytes())
    assert h.hexdigest() == SMOKE_WEIGHTS_SHA256


def test_four_chip_cell_is_correct_and_its_reference_agrees(tmp_path):
    make_root(tmp_path, config=dict(SMOKE_CONFIG, serving=FOUR))
    out = child("cell", tmp_path, "smoke.chat")
    assert out["correct"] is True, out["check"]
    assert out["device"]["count"] == 4 and out["sharded_leaves"] > 0
    assert out["gap_diff"] <= 1e-5


def test_new_family_from_files_alone(tmp_path):
    fam = f"{QKNORM_CONFIG['model_type']}.py"
    assert not (REPO / "bench" / "families" / fam).exists()
    make_root(tmp_path, config=QKNORM_CONFIG, name="qknorm.chat")
    (tmp_path / "bench" / "families" / fam).write_text(QKNORM_FAMILY)
    (tmp_path / "bench" / "reference" / fam).write_text(QKNORM_REFERENCE)
    out = child("cell", tmp_path, "qknorm.chat")
    assert out["correct"] is True, out["check"]
    assert out["device"]["count"] == 4 and out["sharded_leaves"] > 0
    assert out["gap_diff"] <= 1e-5


def test_chips_disagreeing_with_the_file_are_refused(tmp_path):
    bench = make_root(tmp_path, chips=4)
    with pytest.raises(ValueError, match="must agree"):
        run_cell(tmp_path, bench, "smoke.chat", SEED, 1.0, False,
                 time.perf_counter(), peaks=PEAKS["TPU v5e"])


def test_control_is_refused_on_four_chips(tmp_path):
    """The program's TP path refuses int8 weights: calibrate stops before
    it touches a device, with a message, rather than failing mid-run."""
    make_root(tmp_path, config=dict(SMOKE_CONFIG, serving=FOUR))
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "calibrate.py"),
         "--workload", "smoke.chat", "--seconds", "1",
         "--control-seeds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2 and p.stdout == ""
    assert "refuses the int8 control" in p.stderr
