"""Architecture modules (``bench/arch/``).  The dense module gives, bit for
bit, what the harness gave before the model's equations moved into it; and
a toy architecture that exists only in this test (``toy_arch.py``: two
layers, the second attending to a window of 8 keys) runs a serving cell
through ``bench/run.py`` with no other file touched: its weights, its
reference and its windows are the ones used."""
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, costs, reference, serve_cell, train_cell, traffic
from bench.run import load_config
from bench.tests import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Recorded on the CPU from the harness before ``bench/arch/`` existed
# (weights.make, reference.served_gaps, reference.loss_and_grad and the
# counts of serve_cell and costs), by the calls below.
GOLDEN = {
    "weights": {"serve": "4ed48e0500865deb", "train": "55d0d1c3fe0f475a"},
    "served_gaps": {None: ("63df48099b05964b", "0x1.5576940000000p+2"),
                    "fp8": ("cd7d1829a22b60a2", "0x1.8184c00000000p-2")},
    "loss": "0x1.bade440000000p+2", "grads": "f2ca401a99016162",
    "serve_work": {
        "smoke": {"prefill_model_flops": 102570496.0,
                  "decode_model_flops": 26077696.0,
                  "attn_prefill_flops": 5085696.0,
                  "attn_prefill_bytes": 549824.0,
                  "attn_decode_flops": 1763840.0,
                  "attn_decode_bytes": 1156672.0},
        "olmo-1b": {"prefill_model_flops": 19897025822720.0,
                    "decode_model_flops": 1900999606272.0,
                    "attn_prefill_flops": 757209235456.0,
                    "attn_prefill_bytes": 2600894464.0,
                    "attn_decode_flops": 133499453440.0,
                    "attn_decode_bytes": 68934090752.0}},
    "train_flops_per_token": {"smoke": 615168.0, "bert-base": 678620160.0,
                              "olmo-1b": 7463436288.0},
}


def _hash(x) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(x)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


def _smoke(kind):
    m, spec = smoke.serve_cell() if kind == "serve" else smoke.train_cell()
    return m, spec, arch.load(m).make_weights(m, m[kind]["param_dtype"],
                                              smoke.SEED)


def test_a_configuration_without_arch_is_dense():
    assert "arch" not in load_config("olmo-1b")
    assert arch.load(load_config("olmo-1b")) is arch.load({"arch": "dense"})


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_dense_weights_are_the_parents(kind):
    assert _hash(_smoke(kind)[2]) == GOLDEN["weights"][kind]


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_dense_served_gaps_are_the_parents(quant):
    m, _, params = _smoke("serve")
    toks = traffic.rng(smoke.SEED, 9).integers(0, m["vocab"], 70,
                                               dtype=np.int32)
    pad = np.zeros(128, np.int32)
    pad[:70] = toks
    valid = np.zeros(128, bool)
    valid[39:69] = True
    g = np.asarray(arch.load(m).served_gaps(
        reference.sizes_key(m), params, jnp.asarray(pad), jnp.asarray(valid),
        quant=quant, kv="fp2fx8", split=16))
    want_hash, want_max = GOLDEN["served_gaps"][quant]
    assert float(g.max()).hex() == want_max
    assert hashlib.sha256(g.tobytes()).hexdigest()[:16] == want_hash


def test_dense_loss_and_grad_are_the_parents():
    m, job, params = _smoke("train")
    batch = train_cell.make_feed(m, job, smoke.SEED)(0)
    val, grads = arch.load(m).loss_and_grad(
        reference.sizes_key(m), jax.tree.map(lambda x: x.astype(jnp.float32),
                                             params),
        batch, job["z_loss"], job["reference_rows"],
        block=m["train"]["attention_block"])
    assert float(val).hex() == GOLDEN["loss"]
    assert _hash(grads) == GOLDEN["grads"]


@pytest.mark.parametrize("name", ["smoke", "olmo-1b"])
def test_dense_serve_work_is_the_parents(name):
    if name == "smoke":
        m, mix = smoke.serve_cell()
        seed, seconds = smoke.SEED, 10.0
    else:
        m, mix = load_config(name), traffic.load("serve-long")
        seed, seconds = 2147491301, 51.0
    reqs = traffic.serve_requests(mix, seed, seconds, m["vocab"])
    served = {r.rid: [0] * r.max_new for r in reqs}
    assert serve_cell.serve_work(m, m["serve"], reqs, served) == \
        GOLDEN["serve_work"][name]


@pytest.mark.parametrize("name,seq", [("smoke", 32), ("bert-base", 512),
                                      ("olmo-1b", 2048)])
def test_dense_train_flops_per_token_are_the_parents(name, seq):
    m = smoke.train_cell()[0] if name == "smoke" else load_config(name)
    assert costs.train_flops_per_token(m, seq) == \
        GOLDEN["train_flops_per_token"][name]


# --- a new architecture as new files ----------------------------------------

@pytest.fixture(scope="module")
def toy():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.smoke", "toy"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_toy_architecture_serves_and_its_reference_passes_it(toy):
    out = toy["result"]
    assert out["correct"] is True and out["failed"] == 0, out["check"]
    assert set(out["metrics"]) == {"ttft_p95_ms", "tbt_p95_ms",
                                   "output_tokens_per_s", "setup_s"}


def test_toy_reference_holds_the_window(toy):
    """With the windowed layer's attention in play the program (which
    attends to every position) and the toy's reference part."""
    gap, limit = toy["unmuted"]["served_logit_gap"]
    assert gap > limit


def _visible(n_keys, queries, window):
    """(query, key) visibility of ``queries`` over keys 0 .. n_keys - 1."""
    q = np.asarray(queries)[:, None]
    k = np.arange(n_keys)[None, :]
    seen = k <= q
    if window is not None:
        seen &= k > q - window
    return seen


def test_toy_windows_reach_the_work_counts(toy):
    m, _ = smoke.serve_cell()
    H, KV, D, w = m["n_heads"], m["n_kv_heads"], m["d_head"], \
        m["serve"]["prefill_chunk"]
    kv_tok = 2 * KV * (D + 4)                   # fp2fx8: int8 + f32 scale
    want = dict.fromkeys(["attn_prefill_flops", "attn_prefill_bytes",
                          "attn_decode_flops", "attn_decode_bytes"], 0)
    for n, new in toy["requests"]:
        spans = [("prefill", range(s, min(s + w, n)))
                 for s in range(0, n, w)]
        spans += [("decode", [p]) for p in range(n, n + new - 1)]
        for kind, qs in spans:
            for window in smoke.TOY_WINDOWS:
                seen = _visible(qs[-1] + 1, qs, window)
                want[f"attn_{kind}_flops"] += 4 * H * D * int(seen.sum())
                want[f"attn_{kind}_bytes"] += (2 * len(qs) * H * D * 2
                                               + kv_tok
                                               * int(seen.any(0).sum()))
    from bench.arch import dense
    L, d, V = len(smoke.TOY_WINDOWS), m["d_model"], m["vocab"]
    per_tok = 2 * (L * dense.active_matmul_params(m) + V * d)
    lens = [n for n, _ in toy["requests"]]
    dec = [p for n, new in toy["requests"] for p in range(n, n + new - 1)]
    want["prefill_model_flops"] = per_tok * sum(lens) - 2 * V * d * (
        sum(lens) - len(lens)) + sum(
            4 * H * D * int(_visible(n, range(n), window).sum())
            for n in lens for window in smoke.TOY_WINDOWS)
    want["decode_model_flops"] = per_tok * len(dec) + sum(
        4 * H * D * int(_visible(p + 1, [p], window).sum())
        for p in dec for window in smoke.TOY_WINDOWS)
    got = {k: toy["work"][k] for k in want}
    assert got == want
    full = sum(int(_visible(n, range(n), None).sum())
               for n, _ in toy["requests"])
    assert got["attn_prefill_flops"] < 2 * 4 * H * D * full
