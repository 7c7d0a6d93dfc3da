"""Family ``attn_lm``: a decoder of pre-norm causal self-attention and
gated-MLP blocks, configured with Hugging Face ``config.json`` key names.

Maps a configuration onto the program's ``ModelConfig`` (architecture only:
the program's own knobs, such as remat and attention blocking, stay at its
defaults, so that a change to them is a change of the program), and counts
the operations the architecture needs, from shapes. A multiply-add is 2
operations; causal attention counts the keys a query may see;
recomputation under remat is not counted.
"""

from __future__ import annotations

def model_config(c: dict):
    from repro.models.config import LayerSpec, ModelConfig
    if c.get("use_qkv_bias"):
        raise ValueError("attn_lm: the program has no qkv bias")
    d, h = c["hidden_size"], c["num_attention_heads"]
    return ModelConfig(
        name=c["name"], family="dense", d_model=d,
        n_layers=c["num_hidden_layers"], n_heads=h,
        n_kv_heads=c["num_key_value_heads"], head_dim=d // h,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        cycle=(LayerSpec(kind="attn"),), mlp_act=c["hidden_act"], gated=True,
        rope_theta=float(c["rope_theta"]),
        norm_type={"layernorm": "ln", "rmsnorm": "rms"}[c["norm"]],
        norm_eps=float(c["layer_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"])


def _shapes(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "h": h, "kv": c["num_key_value_heads"], "dh": d // h,
            "f": c["intermediate_size"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def matmul_params(c: dict) -> int:
    """Parameters that enter a matrix multiplication per token: every
    layer's projections and the output head (the embedding is a gather)."""
    s = _shapes(c)
    attn = s["d"] * s["h"] * s["dh"] * 2 + s["d"] * s["kv"] * s["dh"] * 2
    per_layer = attn + 3 * s["d"] * s["f"]          # gated MLP: wi, wg, wo
    return s["L"] * per_layer + s["d"] * s["V"]


def mixer_fwd_per_token(c: dict, seq: int) -> float:
    """Forward operations per token of attention beyond its projections,
    averaged over a causal sequence of ``seq`` tokens: QK^T and PV over the
    (i + 1) keys query i may see."""
    s = _shapes(c)
    return 2 * s["h"] * s["dh"] * (seq + 1) * s["L"]
