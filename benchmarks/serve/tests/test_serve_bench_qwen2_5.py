"""qwen2.5-14b: its configuration file against the published widths, and
the engine against the float32 reference at a small size with the 14b's
distinguishing traits (`data/tiny_longdoc.json`: an untied head, 10 query
heads over 2 KV heads, a group of 5 as in the 14b's 40 over 8, head_dim
128, QKV bias, rms_norm_eps 1e-05).

The engine prefills three prompts in one paged batch (20, 33 and 40
tokens: the 20-token prompt's own pad bucket is 32, the batch pads to 64),
then decodes six tokens each through the pool.  Each served token's logits
are compared with `refs/qwen2.py`'s full forward pass over prompt + served
tokens, on the same seeded weights.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serve_bench_util import BENCH, DATA

from loaders import qwen2 as loader
from refs import qwen2 as ref
from repro.core.packed import EncodingConfig
from repro.serving import engine as engine_lib
from repro.serving.config import EngineConfig

# qwen2.5-14b as published (hf Qwen/Qwen2.5-14B config.json).
PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 13824, "num_attention_heads": 40,
    "num_key_value_heads": 8, "num_hidden_layers": 48, "vocab_size": 152064,
    "rope_theta": 1e6, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "use_sliding_window": False, "torch_dtype": "bfloat16", "hidden_act": "silu",
}
PROMPTS = (20, 40, 33)
NEW_TOKENS = 6

# Tolerances, each read on the CPU over seeds 11, 12, 13 and 2**33 + 19:
# the engine computes its activations in bf16, as the configuration states,
# and reads 0.0128-0.0152 (relative error), 0.047-0.061 (largest error in
# logit standard deviations) and 0-0.0031 (mean logit gap); the float8 e4m3
# control (the reference rounded to float8 at every projection's inputs,
# the residual at each block's entry, q, k, v and the attention
# probabilities) reads 0.207-0.289, 0.79-1.06 and 0.084-0.136.  Each limit
# lies between, with room on both sides.  The same reference rounded to
# bf16 at those points reads 0.0092-0.0126, 0.036-0.042 and 0-0.0028: as
# close as the engine, which rounds to bf16 itself, so the control is the
# precision below the configuration's.
REL_ERR = 0.05     # per position: |program - reference| / |reference| over the vocabulary
MAX_ERR_SD = 0.25  # largest |program - reference| in units of the reference logits' SD
MEAN_GAP = 0.02    # mean over served tokens of the reference's best logit less its
                   # logit of the token the program puts first


def test_configuration_is_the_published_model_cut_in_depth():
    with open(os.path.join(BENCH, "configs", "qwen2.5-14b-d8.json")) as f:
        cfg = json.load(f)
    assert cfg["arch"] == "qwen2" and cfg["registry_arch"] == "qwen2.5-14b"
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["num_hidden_layers"] == 8
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert "head_dim" not in cfg  # 5120 / 40 = 128, as published
    assert any(a.startswith("rms_norm_eps") for a in cfg["assumed"])
    assert "six pipeline stages" in cfg["deployment"]
    with open(os.path.join(os.path.dirname(os.path.dirname(BENCH)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "qwen2.5-14b-d8"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmarks/serve/configs/qwen2.5-14b-d8.json"


def _cfg():
    with open(os.path.join(DATA, "tiny_longdoc.json")) as f:
        return json.load(f)


def _served(seed):
    """The engine's requests and, per request, the logits of each served
    token (the decode step's; the batched prefill's are those of its padded
    last position, which the engine does not sample)."""
    cfg = _cfg()
    mcfg = loader.model_config(cfg)
    enc = EncodingConfig(enabled=True, **cfg["encoding"])
    params = loader.program_params(cfg, mcfg, enc, seed)
    eng = engine_lib.Engine(params, mcfg, enc, config=EngineConfig(
        cache_mode="paged", block_size=16, kv_quant="bf16", slots=3, max_seq=128))
    rng = np.random.default_rng(seed)
    reqs = [engine_lib.Request(uid=i, prompt=rng.integers(1, cfg["vocab_size"], n)
                               .astype(np.int32), max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPTS)]
    logits = {r.uid: [] for r in reqs}
    prefills = []
    prefill, decode = eng.prefill_fn, eng.decode_fn

    def seen_prefill(params, tokens, caches, *rest):
        prefills.append(tokens.shape)
        return prefill(params, tokens, caches, *rest)

    def kept(params, caches, token, pos):
        nxt, out, caches = decode(params, caches, token, pos)
        last = np.asarray(out[:, -1], np.float32)
        for s, r in enumerate(eng.slot_req):
            if r is not None:
                logits[r.uid].append(last[s])
        return nxt, out, caches

    eng.prefill_fn, eng.decode_fn = seen_prefill, kept
    for r in reqs:
        assert eng.submit(r)
    eng.run()
    assert prefills == [(3, 64)]
    return cfg, reqs, {u: np.stack(v) for u, v in logits.items()}


def _reference(cfg, seed, seq, low=False):
    """The reference's logits at every position of `seq` (float32, matmul
    precision highest; with `low`, the float8 control)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    key = ref.base_key(seed)
    toks = np.zeros((1, ref.Q_CHUNK), np.int32)
    toks[0, :len(seq)] = seq
    x = ref._embed(items, jnp.asarray(toks), key)
    n = jnp.asarray([len(seq)], jnp.int32)
    for layer in range(cfg["num_hidden_layers"]):
        x = ref._run_layer(items, low, x, key, layer, n)
    g = ref.draw_global(cfg, key)
    h = ref._rms(x[0, :len(seq)], g["final_norm"], cfg["rms_norm_eps"])
    return np.asarray(ref._proj(h, g["head"], low))


def _errors(got, want):
    """(relative error, largest error in reference SDs, mean logit gap)."""
    d = got - want
    rel = np.sqrt((d ** 2).sum(-1) / (want ** 2).sum(-1)).max()
    gap = want.max(-1) - want[np.arange(len(want)), got.argmax(-1)]
    return float(rel), float(np.abs(d).max() / want.std()), float(gap.mean())


@pytest.fixture(scope="module", params=[11, 2**33 + 19], ids=["seed11", "seed2e33"])
def compared(request):
    seed = request.param
    cfg, reqs, logits = _served(seed)
    out = {"program": [], "control": []}
    for r in reqs:
        assert len(r.generated) == NEW_TOKENS
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
        first = len(r.prompt) - 1
        want = _reference(cfg, seed, seq)[first:]
        out["program"].append(_errors(logits[r.uid], want))
        out["control"].append(_errors(_reference(cfg, seed, seq, low=True)[first:], want))
    jax.clear_caches()
    return {k: np.max(np.asarray(v), axis=0) for k, v in out.items()}


def test_engine_logits_match_the_reference(compared):
    rel, sd, gap = compared["program"]
    assert rel <= REL_ERR and sd <= MAX_ERR_SD and gap <= MEAN_GAP, compared["program"]


def test_float8_control_fails_every_tolerance(compared):
    rel, sd, gap = compared["control"]
    assert rel > REL_ERR and sd > MAX_ERR_SD and gap > MEAN_GAP, compared["control"]
