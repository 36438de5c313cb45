"""Operations and bytes that the measured work needs, from its shapes.

Kept with the benchmark (beside ``peaks.json``) so that every change is
judged on the same work: these count what the algorithm needs, not what a
particular implementation pads or recomputes.
"""
from __future__ import annotations

F32 = 4


def auction_bid(n: int, m: int) -> tuple:
    """One forward-bidding round over an n x m agent-level market:
    (operations, bytes). Reads W (n x m float32), ask and ask2 (m each) and
    the active mask (n); writes best and winner (m each) and wants (n).
    About 8 operations per market entry: the profit, the top-2 scan (max,
    argmax, masked re-scan, max), the bid and the segment max. The bound is
    the bytes: a bidding round is a streaming pass over W."""
    ops = 8 * n * m
    nbytes = F32 * (n * m + 2 * m + n) + F32 * (2 * m + n)
    return ops, nbytes


def dense_lm_call(model: dict, new_tokens: int, context: int,
                  head_rows: int = 1) -> tuple:
    """One forward call of the dense decoder: ``new_tokens`` tokens computed
    after ``context`` cached ones, logits for ``head_rows`` positions.
    Returns (operations, bytes moved at the least).

    Operations: 2 per multiply-add of every projection for each new token,
    the attention scores and mixing over the causal context, and the LM head.
    Bytes: every layer's weights and the LM head read once per call (bf16,
    the configuration's dtype), the new tokens' embedding rows, the cached
    keys and values read, and the new ones written."""
    L = int(model["num_hidden_layers"])
    D = int(model["hidden_size"])
    H = int(model["num_attention_heads"])
    K = int(model["num_key_value_heads"])
    hd = int(model["head_dim"])
    F = int(model["intermediate_size"])
    V = int(model["vocab_size"])
    w = 2  # bytes per weight and cache element (bfloat16)
    t, c = int(new_tokens), int(context)
    proj = D * (H + 2 * K) * hd + H * hd * D + 3 * D * F
    # causal: token k of the block sees c + k + 1 positions
    seen = t * c + t * (t + 1) // 2
    ops = L * (2 * proj * t + 4 * H * hd * seen) + 2 * D * V * head_rows
    layer_w = proj + 2 * D + 2 * hd
    kv_read = L * 2 * K * hd * c * w
    kv_write = L * 2 * K * hd * t * w
    nbytes = (L * layer_w + D * V + D) * w + t * D * w + kv_read + kv_write
    return ops, nbytes


def served_request(model: dict, n_prompt: int, n_hit: int,
                   n_gen: int) -> tuple:
    """The engine calls one served request makes: a prefill of the prompt
    (no cached tokens) or an extend of its uncached suffix, then one decode
    step per generated token. Returns (operations, bytes)."""
    ops = nbytes = 0
    new = n_prompt - n_hit
    if new > 0:
        o, b = dense_lm_call(model, new, n_hit)
        ops, nbytes = ops + o, nbytes + b
    for k in range(n_gen):
        o, b = dense_lm_call(model, 1, n_prompt + k)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def fused_route(n: int, m: int, prompt_tokens: int, rounds: int) -> int:
    """Operations one fused routing step needs: the Eq.-4 prefix match
    (compare and running product for every prompt token against each of the
    m agents' ledger rows) and ``rounds`` bidding rounds over n x m."""
    return 2 * prompt_tokens * m + rounds * auction_bid(n, m)[0]
