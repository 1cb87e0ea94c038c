"""A configuration file's model as the program runs it, and its weights.

The weights are the benchmark's own: drawn from ``--seed`` on the device in
one jitted call, in the dtype the configuration serves them in, under the
canonical names the plain reference reads (``reference/lm.py``), and then
arranged into the program's parameter tree. The reference never sees what
the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def seed_words(seed: int):
    """A seed as two uint32 words, so that jitted set-up takes it as an
    argument (one program for every seed) and keeps all of its bits
    (``PRNGKey`` of a Python int drops the high word)."""
    import numpy as np
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def key_of(words, *salt: int):
    """The PRNG key of ``seed_words`` (traced or not), folded with ``salt``."""
    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def program_config(c: dict, **overrides):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.models.config import ModelConfig
    kw = dict(
        name=c["name"], arch_type="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv=c["num_key_value_heads"], head_dim=c.get("head_dim", 0),
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        qkv_bias=c["attention_bias"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        remat=True, scan_layers=True)
    kw.update(overrides)
    return ModelConfig(**kw)


def canonical_shapes(c: dict) -> dict:
    d, L, F, V = (c["hidden_size"], c["num_hidden_layers"],
                  c["intermediate_size"], c["vocab_size"])
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    layers = {"ln1": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
              "wv": (L, d, kv), "wo": (L, q, d), "ln2": (L, d),
              "wg": (L, d, F), "wu": (L, d, F), "wd": (L, F, d)}
    if c["attention_bias"]:
        layers.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    out = {"embed": (V, d), "final_norm": (d,), "layers": layers}
    if not c["tie_word_embeddings"]:
        out["unembed"] = (d, V)
    return out


def make_weights(c: dict, words) -> dict:
    """Canonical weights: N(0, initializer_range) each (norm scales are
    stored as offsets from 1, so they are 1 + N(0, initializer_range)), the
    token embedding N(0, embedding_init_std). Call under ``jax.jit`` with
    ``words`` from :func:`seed_words`."""
    dtype = jnp.dtype(c["torch_dtype"])
    key = key_of(words, 0)
    shapes = canonical_shapes(c)
    std = {k: c["initializer_range"] for k in shapes}
    std["embed"] = c.get("embedding_init_std", c["initializer_range"])
    out = {}
    for i, (name, s) in enumerate(sorted(shapes.items())):
        if name == "layers":
            out[name] = {n: _normal(jax.random.fold_in(key, 100 + j), v,
                                    std[name], dtype)
                         for j, (n, v) in enumerate(sorted(s.items()))}
        else:
            out[name] = _normal(jax.random.fold_in(key, i), s, std[name], dtype)
    return out


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def to_program(w: dict) -> dict:
    """Canonical weights -> the program's tree for a dense model whose
    layers are scanned as one group (``models/lm.py init_lm``)."""
    lw = w["layers"]
    mix = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
           if k in lw}
    tree = {"embed": w["embed"], "final_norm": w["final_norm"],
            "groups": [{"ln1": lw["ln1"], "ln2": lw["ln2"], "mix": mix,
                        "mlp": {k: lw[k] for k in ("wg", "wu", "wd")}}]}
    if "unembed" in w:
        tree["unembed"] = w["unembed"]
    return tree


def from_program(tree: dict) -> dict:
    """The program's tree -> canonical names (inverse of ``to_program``)."""
    g = tree["groups"][0]
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"],
           "layers": {"ln1": g["ln1"], "ln2": g["ln2"], **g["mix"], **g["mlp"]}}
    if "unembed" in tree:
        out["unembed"] = tree["unembed"]
    return out


def check_layout(c: dict, cfg) -> None:
    """Fail unless ``to_program`` builds exactly the tree ``init_lm`` makes."""
    from repro.models.lm import init_lm
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: to_program(make_weights(c, seed_words(0))))
    sw = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    sg = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), got)
    if sw != sg:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"program {sw}\nbench {sg}")
