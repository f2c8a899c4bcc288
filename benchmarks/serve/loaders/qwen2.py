"""Load a Qwen2 configuration file into the program under test: its model
configuration, and the seeded plain weights of `refs/qwen2.py` in the
program's own parameter format (weights packed by the program's packing
function), made on the device in one jitted call."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from refs import qwen2 as ref
from repro.configs import registry
from repro.kernels import ops
from repro.models import transformer as T

# Published key -> field of the program's ModelConfig.
FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(cfg: dict):
    """The program's ModelConfig for this file: the registry entry, with
    every published value set as the file states it."""
    base = registry.get_config(cfg["registry_arch"])
    over = {field: cfg[key] for key, field in FIELDS.items()}
    over.update(head_dim=ref.head_dim(cfg), qkv_bias=True, dtype=cfg["torch_dtype"],
                sliding_window=0, mlp_kind="swiglu", norm_kind="rmsnorm")
    return dataclasses.replace(base, **over)


def _linear(w, b, abstract, pack):
    out = {}
    for name, leaf in abstract.items():
        if name == "w_packed":
            out[name] = pack(w)
        elif name == "b":
            out[name] = b.astype(leaf.dtype)
        else:
            raise KeyError(f"unknown linear leaf {name!r}: the loader does not know "
                           "this parameter format")
        if out[name].shape != leaf.shape:
            raise ValueError(f"{name}: loaded {out[name].shape}, program wants {leaf.shape}")
    return out


def program_params(cfg: dict, mcfg, enc, seed: int):
    """The program's parameter tree holding the seeded plain weights."""
    abstract = jax.eval_shape(lambda k: T.model_init(k, mcfg, enc), jax.random.PRNGKey(0))
    n_layers = cfg["num_hidden_layers"]

    def pack(w):
        return ops.pack_rhs(w, target=enc.target, shard_multiple=enc.shard_multiple)

    def build(key):
        g = ref.draw_global(cfg, key)
        layers = jax.vmap(lambda i: ref.draw_layer(cfg, key, i))(jnp.arange(n_layers))
        stacked_pack = jax.vmap(pack)
        (ga,) = abstract["groups"]
        ab, mb = ga["attn"], ga["mlp"]
        group = {
            "ln1": {"scale": layers["ln1"]},
            "ln2": {"scale": layers["ln2"]},
            "attn": {
                "wq": _linear(layers["wq"], layers["bq"], ab["wq"], stacked_pack),
                "wk": _linear(layers["wk"], layers["bk"], ab["wk"], stacked_pack),
                "wv": _linear(layers["wv"], layers["bv"], ab["wv"], stacked_pack),
                "wo": _linear(layers["wo"], None, ab["wo"], stacked_pack),
            },
            "mlp": {n: _linear(layers[n], None, mb[n], stacked_pack)
                    for n in ("w_gate", "w_up", "w_down")},
        }
        v_pad = abstract["embed"].shape[0]
        embed = jnp.pad(g["embed"], ((0, v_pad - cfg["vocab_size"]), (0, 0)))
        params = {"embed": embed.astype(abstract["embed"].dtype),
                  "final_norm": {"scale": g["final_norm"]},
                  "groups": (group,)}
        if "head" in abstract:
            params["head"] = _linear(g["head"], None, abstract["head"], pack)
        return params

    params = jax.jit(build)(ref.base_key(seed))
    want = jax.tree.structure(abstract)
    if jax.tree.structure(params) != want:
        raise ValueError(f"loaded tree {jax.tree.structure(params)} is not the program's {want}")
    for got, exp in zip(jax.tree.leaves(params), jax.tree.leaves(abstract)):
        if got.shape != exp.shape or got.dtype != exp.dtype:
            raise ValueError(f"loaded leaf {got.shape} {got.dtype}, program wants "
                             f"{exp.shape} {exp.dtype}")
    return params
