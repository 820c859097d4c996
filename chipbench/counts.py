"""Operations and bytes that the work needs, computed from shapes alone.

These are the numerators of every roofline share and utilization that the
benchmark reports.  They count what the algorithm needs at the call's shapes,
never what an implementation happens to do: a causal attention call needs the
query-key pairs on or below the diagonal, so a kernel that also computes the
masked blocks reads lower, and one that skips them reads higher.
"""

from __future__ import annotations


def causal_pairs(sq: int, sk: int, q_offset: int = 0) -> int:
    """Query-key pairs (i, j) with j <= i + q_offset and j < sk."""
    total = 0
    # Rows whose window is clipped by sk, and rows that see i + q_offset + 1 keys.
    full = max(0, min(sq, sk - q_offset))
    total += full * (full + 1) // 2 + full * q_offset
    total += (sq - full) * sk
    return total


def flash_fwd(batch, sq, sk, heads, kv_heads, head_dim, *, causal=True,
              q_offset=0, itemsize=2, with_lse=False):
    """(flops, bytes) of one forward call: QK^T and PV over the needed pairs;
    q, k, v read once and o written once (plus 4 bytes per row of LSE)."""
    pairs = causal_pairs(sq, sk, q_offset) if causal else sq * sk
    flops = 4 * head_dim * heads * batch * pairs
    nbytes = itemsize * batch * (2 * sq * heads * head_dim + 2 * sk * kv_heads * head_dim)
    if with_lse:
        nbytes += 4 * batch * heads * sq
    return flops, nbytes


def flash_bwd(batch, sq, sk, heads, kv_heads, head_dim, *, causal=True,
              q_offset=0, itemsize=2):
    """(flops, bytes) of one backward call (dq, dk and dv together): the five
    products of the FlashAttention-2 backward (QK^T recomputed, dP, dV, dQ,
    dK) over the needed pairs; q, k, v, o, dO and the LSE read once, and dq,
    dk, dv written once."""
    pairs = causal_pairs(sq, sk, q_offset) if causal else sq * sk
    flops = 10 * head_dim * heads * batch * pairs
    q_side = batch * sq * heads * head_dim
    kv_side = batch * sk * kv_heads * head_dim
    nbytes = itemsize * (4 * q_side + 2 * kv_side) + 4 * batch * heads * sq
    nbytes += itemsize * (q_side + 2 * kv_side)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def matmul_params(arch: dict) -> tuple[int, int]:
    """(parameters in the layers' matmuls, parameters of the output head).
    The embedding lookup is not a matmul; a tied head still is one."""
    d, hd = arch["d_model"], arch["head_dim"]
    attn = d * arch["heads"] * hd * 2 + d * arch["kv_heads"] * hd * 2
    mlp = 3 * d * arch["d_ff"]
    return arch["layers"] * (attn + mlp), arch["vocab"] * d


def attention_flops(arch: dict, n: int) -> int:
    """Forward causal attention operations of one sequence of n tokens."""
    return 4 * arch["head_dim"] * arch["heads"] * arch["layers"] * causal_pairs(n, n)


def train_flops(arch: dict, seq: int) -> int:
    """Model operations of one training sequence: forward and backward
    (3 x forward), matmul parameters and causal attention; recomputation
    under remat is not counted."""
    layers, head = matmul_params(arch)
    return 3 * (2 * (layers + head) * seq + attention_flops(arch, seq))


def prefill_flops(arch: dict, n: int) -> int:
    """Model operations of one prefill of n true prompt tokens: the layers'
    matmuls for every token, the head once (for the token that is sampled)
    and causal attention.  Padding to a bucket is not work."""
    layers, head = matmul_params(arch)
    return 2 * layers * n + 2 * head + attention_flops(arch, n)


def decode_flops(arch: dict, keys: int) -> int:
    """Model operations of one decode token that attends to ``keys`` cached
    keys (itself included): the layers' matmuls, the head, and attention."""
    layers, head = matmul_params(arch)
    return 2 * (layers + head) + 4 * arch["head_dim"] * arch["heads"] * arch["layers"] * keys
