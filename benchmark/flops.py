"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no later PR can move a utilisation by
recounting. Nothing here counts recomputation, padding or copies: a program
that does more work than this reads as a lower share, never a higher one.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of `device_kind`; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return table[device_kind]


def encoder_matmul_params(m):
    """Weights that every token is multiplied by in a BERT-style encoder
    with an MLM head over all positions (transform + tied embedding). The
    pooler and the NSP head run once a sequence and are left out."""
    h, i = m["hidden_size"], m["intermediate_size"]
    return (m["num_hidden_layers"] * (4 * h * h + 2 * h * i)
            + h * h + m["vocab_size"] * h)


def train_flops_per_token(m, seq):
    """Forward + backward of one token: 6 per matmul weight, plus
    bidirectional attention (QK^T and PV are 2*seq*hidden each forward,
    three times that with the backward pass)."""
    return (6 * encoder_matmul_params(m)
            + 12 * m["num_hidden_layers"] * seq * m["hidden_size"])


def decoder_weight_bytes(m, itemsize=4):
    """Bytes of weights one decode step has to read: every block's
    projections and feed-forward, and the tied head."""
    h, i = m["hidden_size"], m["intermediate_size"]
    return itemsize * (m["num_layers"] * (4 * h * h + 2 * h * i)
                       + m["vocab_size"] * h)


def kv_bytes_per_token(m, itemsize=4):
    """Keys and values of one cached position, all layers."""
    return 2 * m["num_layers"] * m["hidden_size"] * itemsize
