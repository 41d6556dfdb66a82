"""The benchmark's arithmetic: operations and bytes from shapes, the
peaks table, and the percentiles.  Nothing here reads the program or
its compiled code, so a change to the program cannot move the
yardstick."""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device is not in the peaks table."""


def peaks(device_kind, table=None):
    """{"bf16_flops", "hbm_bytes_per_s", "ici_bits_per_s"} of one chip."""
    table = table or json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# model shapes
# ---------------------------------------------------------------------------


def matmul_params(m):
    """Parameters that take part in a matrix product per token: the
    layers' projections and MLP, and the output head (tied or not).
    The embedding lookup, norms and biases do no matrix work."""
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    return m["num_layers"] * layer + m["vocab_size"] * d


def all_params(m):
    """Every parameter of the model, as the trainer's state holds it."""
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    layer = (d * (h + 2 * kv) * hd + h * hd * d + 3 * d * m["d_ff"]
             + 2 * d)
    if m["qkv_bias"]:
        layer += (h + 2 * kv) * hd
    head = 0 if m["tie_embeddings"] else d * m["vocab_size"]
    return m["num_layers"] * layer + m["vocab_size"] * d + head + d


def attention_flops_fwd(m, q_len, ctx_start=0):
    """Forward attention FLOPs (QK^T and PV) of q_len causal queries
    at positions ctx_start .. ctx_start + q_len - 1, each attending to
    itself and everything before it."""
    keys = q_len * ctx_start + q_len * (q_len + 1) / 2
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * keys


def train_step_flops(m, agents, batch, seq):
    """Model FLOPs of one superstep: forward and backward (3x the
    forward) of every agent's batch, with no recomputation counted."""
    seqs = agents * batch
    fwd = (2 * matmul_params(m) * seq
           + attention_flops_fwd(m, seq)) * seqs
    return 3 * fwd


def serve_request_flops(m, prompt_len, output_len):
    """Forward FLOPs of serving one request: its prompt, then one
    decode position per output token after the first (the first comes
    out of the prompt's last position)."""
    decoded = max(output_len - 1, 0)
    tokens = prompt_len + decoded
    return (2 * matmul_params(m) * tokens
            + attention_flops_fwd(m, tokens))


def prox_update_bytes(m):
    """Least bytes one agent's eq. 15/12b update moves: read x, g, zsum
    and write x_new and the token credit, float32 each."""
    return 20 * all_params(m)


# ---------------------------------------------------------------------------
# percentiles
# ---------------------------------------------------------------------------


def percentile(values, q):
    """The q-th percentile (0-100) by nearest rank: the smallest value
    with at least q% of the samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def weighted_percentile(pairs, q):
    """Nearest-rank percentile of (value, weight) pairs, each value
    counted weight times (weights are whole numbers)."""
    pairs = sorted((v, int(w)) for v, w in pairs if w > 0)
    total = sum(w for _, w in pairs)
    if total == 0:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * total))
    seen = 0
    for v, w in pairs:
        seen += w
        if seen >= rank:
            return v
    return pairs[-1][0]


def beyond(count, q):
    """How many of `count` samples lie beyond the q-th percentile."""
    return count - max(1, math.ceil(q / 100.0 * count))
