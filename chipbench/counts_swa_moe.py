"""Operations and bytes of a model with sliding-window and full attention
layers and a sparse expert layer in every layer (Mellum2), from shapes
alone, as ``counts.py`` counts those of dense models.  Attention counts the
query-key pairs each layer's mask needs: causal pairs in a full layer, the
pairs of a window of ``window`` keys (the query's own included) in a
sliding one.  Matmuls count the active parameters: the attention
projections, the router and the ``top_k`` experts a token is sent to."""

from __future__ import annotations

from .counts import causal_pairs

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def shape_of(conf: dict) -> dict:
    """The widths and layer kinds of a configuration file."""
    layers = conf["num_hidden_layers"]
    return {
        "layers": layers,
        "kinds": [KINDS[k] for k in conf["layer_types"][:layers]],
        "d_model": conf["hidden_size"],
        "heads": conf["num_attention_heads"],
        "kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"],
        "vocab": conf["vocab_size"],
        "experts": conf["num_experts"],
        "top_k": conf["num_experts_per_tok"],
        "d_expert": conf["moe_intermediate_size"],
        "window": conf["sliding_window"],
    }


def window_pairs(n: int, window: int) -> int:
    """Query-key pairs of n causal rows that each see at most ``window``
    keys: W(W+1)/2 + (n - W)W for n > W."""
    if n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def flash_fwd_window(batch, n, heads, kv_heads, head_dim, window, *, itemsize=2):
    """(flops, bytes) of one windowed forward call over n rows: QK^T and PV
    over the window's pairs; q, k, v read once and o written once."""
    flops = 4 * head_dim * heads * batch * window_pairs(n, window)
    nbytes = itemsize * batch * (2 * n * heads * head_dim + 2 * n * kv_heads * head_dim)
    return flops, nbytes


def active_params(s: dict) -> tuple[int, int]:
    """(active parameters in the layers' matmuls, parameters of the head)."""
    d, hd = s["d_model"], s["head_dim"]
    attn = d * s["heads"] * hd * 2 + d * s["kv_heads"] * hd * 2
    moe = d * s["experts"] + s["top_k"] * 3 * d * s["d_expert"]
    return s["layers"] * (attn + moe), s["vocab"] * d


def attention_flops(s: dict, n: int) -> int:
    """Forward attention operations of one sequence of n tokens."""
    per = 4 * s["head_dim"] * s["heads"]
    return sum(per * (causal_pairs(n, n) if k == "full" else window_pairs(n, s["window"]))
               for k in s["kinds"])


def prefill_flops(s: dict, n: int) -> int:
    """Model operations of one prefill of n true prompt tokens: the active
    matmuls for every token, the head once, and attention."""
    layers, head = active_params(s)
    return 2 * layers * n + 2 * head + attention_flops(s, n)


def decode_flops(s: dict, keys: int) -> int:
    """Model operations of one decode token that attends to ``keys`` cached
    keys (itself included), at most ``window`` of them in a sliding layer."""
    layers, head = active_params(s)
    per = 4 * s["head_dim"] * s["heads"]
    att = sum(per * (keys if k == "full" else min(keys, s["window"])) for k in s["kinds"])
    return 2 * (layers + head) + att
