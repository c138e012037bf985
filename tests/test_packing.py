"""Sequence packing pipeline tests: the io.packing collator, the
token-level loss-mask machinery in Model.fit/evaluate, and the
composition with PR 4's tail bucketing (a partial final pack is just a
pack with more masked tokens — one compile per epoch, never a double
mask).
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.monitor import stat_get, stat_reset
from paddle_tpu.io import (DataLoader, Dataset, PackingCollator,
                           suggest_rows)
from paddle_tpu.io.packing import _fields_of
from paddle_tpu.parallel.mesh import set_mesh
from paddle_tpu.static.input_spec import InputSpec

VOCAB, DIM, HEADS, T = 32, 16, 2, 64


@pytest.fixture
def clean_mesh():
    yield
    set_mesh(None)


def _seqs(n, seed=0, lo=4, hi=T):
    rng = np.random.RandomState(seed)
    lengths = np.clip(np.round(np.exp(rng.normal(2.3, 0.7, n))).astype(int),
                      lo, hi)
    return [(rng.randint(0, VOCAB, (L,)).astype("int64"),
             rng.randint(0, VOCAB, (L,)).astype("int64"))
            for L in lengths]


class SeqData(Dataset):
    def __init__(self, seqs):
        self.seqs = seqs

    def __len__(self):
        return len(self.seqs)

    def __getitem__(self, i):
        return self.seqs[i]


class PackedLM(nn.Layer):
    """Embedding + segment-masked causal attention + LM head — the
    packed-training shape (dense fallback path on the CPU mesh)."""

    def __init__(self, vocab=VOCAB, dim=DIM, heads=HEADS, max_t=T):
        super().__init__()
        self.heads = heads
        self.emb = nn.Embedding(vocab, dim)
        self.pos = nn.Embedding(max_t, dim)
        self.qkv = nn.Linear(dim, 3 * dim)
        self.head = nn.Linear(dim, vocab)

    def forward(self, toks, seg, pos):
        x = self.emb(toks) + self.pos(pos)
        B, S = toks.shape[0], toks.shape[1]
        d = x.shape[-1]
        qkv = self.qkv(x).reshape(
            [B, S, 3, self.heads, d // self.heads]).transpose(
            [2, 0, 3, 1, 4])
        o = F.scaled_dot_product_attention(qkv[0], qkv[1], qkv[2],
                                           is_causal=True, segment_ids=seg)
        x = x + o.transpose([0, 2, 1, 3]).reshape([B, S, d])
        return self.head(x)


def _packed_model(rows_t=T, lr=0.01, seed=0):
    paddle.seed(seed)
    net = PackedLM(max_t=rows_t)
    model = paddle.Model(
        net,
        inputs=[InputSpec([None, rows_t], "int64", "toks"),
                InputSpec([None, rows_t], "int32", "seg"),
                InputSpec([None, rows_t], "int32", "pos")],
        labels=[InputSpec([None, rows_t], "int64", "labels")])
    opt = paddle.optimizer.Adam(lr, parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    model._dist_ctx = None
    return model, net


# ---------------------------------------------------------------------------
# collator
# ---------------------------------------------------------------------------

def test_collator_layout_and_first_fit():
    samples = [(np.arange(10, dtype=np.int64),
                np.arange(10, dtype=np.int64) + 100),
               (np.arange(20, dtype=np.int64),
                np.arange(20, dtype=np.int64) + 100),
               (np.arange(6, dtype=np.int64),
                np.arange(6, dtype=np.int64) + 100)]
    coll = PackingCollator(max_tokens=32, rows=2)
    toks, seg, pos, labels, mask = coll(samples)
    for a in (toks, seg, pos, labels, mask):
        assert a.shape == (2, 32)
    # first-fit: 10 and 20 share row 0 (10+20<=32); 6 opens row 1
    np.testing.assert_array_equal(toks[0, :10], np.arange(10))
    np.testing.assert_array_equal(toks[0, 10:30], np.arange(20))
    np.testing.assert_array_equal(toks[1, :6], np.arange(6))
    np.testing.assert_array_equal(labels[0, 10:30], np.arange(20) + 100)
    # segment ids: 0 then 1 in row 0, pad tail gets the NEXT id (2)
    np.testing.assert_array_equal(seg[0, :10], 0)
    np.testing.assert_array_equal(seg[0, 10:30], 1)
    np.testing.assert_array_equal(seg[0, 30:], 2)
    np.testing.assert_array_equal(seg[1, 6:], 1)
    assert (np.diff(seg, axis=1) >= 0).all()   # splash contract
    # positions restart per segment
    np.testing.assert_array_equal(pos[0, 10:30], np.arange(20))
    # mask marks exactly the real tokens
    assert mask.sum() == 36
    assert coll.last_fill_ratio == 36 / 64.0
    assert coll.emits_token_mask


def test_collator_drop_and_truncate():
    coll = PackingCollator(max_tokens=16, rows=1)
    long = np.arange(40, dtype=np.int64)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d0 = stat_get("STAT_packing_dropped_seqs")
        t0 = stat_get("STAT_packing_truncated_seqs")
        toks, seg, pos, mask = coll([long, np.arange(10, dtype=np.int64)])
        assert stat_get("STAT_packing_truncated_seqs") == t0 + 1
        assert stat_get("STAT_packing_dropped_seqs") == d0 + 1
        assert any("dropped" in str(x.message) for x in w)
    # the truncated 40-seq fills the single row; the 10-seq was dropped
    np.testing.assert_array_equal(toks[0], np.arange(16))
    assert mask.sum() == 16


def test_collator_pad_policy_one_per_row():
    samples = [(np.arange(5, dtype=np.int64),) * 2,
               (np.arange(9, dtype=np.int64),) * 2]
    toks, seg, pos, labels, mask = PackingCollator(
        16, rows=2, policy="pad")(samples)
    np.testing.assert_array_equal(toks[0, :5], np.arange(5))
    np.testing.assert_array_equal(toks[1, :9], np.arange(9))
    assert (seg[0, :5] == 0).all() and (seg[0, 5:] == 1).all()
    assert mask.sum() == 14


def test_collator_errors():
    with pytest.raises(ValueError, match="policy"):
        PackingCollator(16, 2, policy="best_fit")
    with pytest.raises(ValueError, match="equal length"):
        _fields_of((np.arange(4), np.arange(5)))
    with pytest.raises(ValueError, match="empty batch"):
        PackingCollator(16, 2)([])


def test_suggest_rows():
    assert suggest_rows([8, 8, 8, 8], batch_size=4, max_tokens=16) == 3
    assert suggest_rows([100], batch_size=1, max_tokens=16) == 2


def test_suggest_rows_fills_a_long_tail_corpus_without_drops():
    """Packs sized by `suggest_rows` over a clipped-lognormal length
    distribution (most sequences short, a heavy tail near max_tokens)
    fill >= 0.8 of their slots and drop nothing."""
    T, B = 128, 32
    rng = np.random.RandomState(7)
    lengths = np.clip(np.round(np.exp(rng.normal(
        np.log(T / 6.0), 0.9, 320))).astype(int), 4, T)
    coll = PackingCollator(T, suggest_rows(lengths, B, T))
    t0, s0 = stat_get("STAT_packing_tokens"), stat_get("STAT_packing_slots")
    d0 = stat_get("STAT_packing_dropped_seqs")
    for i in range(0, len(lengths), B):
        coll([np.zeros(L, np.int64) for L in lengths[i:i + B]])
    assert stat_get("STAT_packing_dropped_seqs") == d0
    assert stat_get("STAT_packing_tokens") - t0 == lengths.sum()
    fill = lengths.sum() / (stat_get("STAT_packing_slots") - s0)
    assert fill >= 0.8, fill


def test_collator_counters_cumulative_fill():
    p0 = stat_get("STAT_packing_packs")
    f0 = stat_get("STAT_packing_fill_ratio_pct")
    coll = PackingCollator(16, rows=1)
    coll([np.arange(8, dtype=np.int64)])     # fill 50%
    coll([np.arange(16, dtype=np.int64)])    # fill 100%
    assert stat_get("STAT_packing_packs") == p0 + 2
    assert stat_get("STAT_packing_fill_ratio_pct") == f0 + 150


# ---------------------------------------------------------------------------
# fit/evaluate token-mask machinery
# ---------------------------------------------------------------------------

def _manual_masked_ce(model, batch):
    """Token-masked cross-entropy computed by hand from the model's own
    logits — what eval_batch must equal (NO double masking, real-token
    normalization)."""
    toks, seg, pos, labels, mask = batch
    logits = model.predict_batch([toks, seg, pos])
    logits = np.asarray(logits[0] if isinstance(logits, (list, tuple))
                        else logits).astype("float64")
    z = logits - logits.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    nll = -np.take_along_axis(logp, np.asarray(labels)[..., None],
                              axis=-1)[..., 0]
    m = np.asarray(mask)
    return float((nll * m).sum() / m.sum())


def test_fit_packed_one_compile_and_correct_loss():
    """2-epoch packed fit over a dataset whose final pack is partial:
    exactly ONE train-step compile, ZERO tail row-pads (the tail
    machinery must stay off), and the packed eval loss equals the
    hand-computed token-masked CE."""
    seqs = _seqs(26, seed=1)            # 26 seqs, bs 8 -> 3 full + tail 2
    rows = suggest_rows([len(s[0]) for s in seqs], 8, T, headroom=1.6)
    coll = PackingCollator(T, rows)
    loader = DataLoader(SeqData(seqs), batch_size=8, shuffle=False,
                        drop_last=False, collate_fn=coll)
    model, net = _packed_model()
    c0 = stat_get("STAT_train_step_compiles")
    tp0 = stat_get("STAT_tail_pad_batches")
    d0 = stat_get("STAT_packing_dropped_seqs")
    model.fit(loader, epochs=2, verbose=0, log_freq=1)
    assert stat_get("STAT_train_step_compiles") == c0 + 1
    assert stat_get("STAT_tail_pad_batches") == tp0  # no row padding
    assert stat_get("STAT_packing_dropped_seqs") == d0
    w = net.head.weight.numpy()
    assert np.isfinite(w).all()

    # loss correctness on the PARTIAL tail pack (more masked tokens)
    tail = coll(seqs[24:])
    lv, _ = model.eval_batch(list(tail[:3]), [tail[3]], loss_mask=tail[4])
    manual = _manual_masked_ce(model, tail)
    assert abs(float(lv) - manual) < 5e-4, (float(lv), manual)


def test_fit_packed_loss_decreases():
    seqs = _seqs(32, seed=2)
    rows = suggest_rows([len(s[0]) for s in seqs], 8, T, headroom=1.6)
    coll = PackingCollator(T, rows)
    loader = DataLoader(SeqData(seqs), batch_size=8, shuffle=False,
                        drop_last=False, collate_fn=coll)
    model, _ = _packed_model(lr=0.05, seed=3)
    before = model.evaluate(loader, verbose=0)["loss"]
    model.fit(loader, epochs=5, verbose=0, log_freq=1)
    after = model.evaluate(loader, verbose=0)["loss"]
    assert after < before


def test_evaluate_packed_matches_manual_mean():
    """evaluate() weights each pack's real-token-normalized loss by its
    real-token count, so the pass loss is the true per-token mean —
    a near-empty tail pack must not count like a full one."""
    seqs = _seqs(16, seed=4)
    coll = PackingCollator(T, suggest_rows(
        [len(s[0]) for s in seqs], 8, T, headroom=1.6))
    loader = DataLoader(SeqData(seqs), batch_size=8, shuffle=False,
                        collate_fn=coll)
    model, _ = _packed_model(seed=5)
    logs = model.evaluate(loader, verbose=0)
    packs = [coll(seqs[i:i + 8]) for i in (0, 8)]
    per = [_manual_masked_ce(model, p) for p in packs]
    wts = [float(p[4].sum()) for p in packs]
    assert wts[0] != wts[1]  # the weighting must actually matter
    manual = float(np.average(per, weights=wts))
    assert abs(logs["loss"] - manual) < 5e-4
    assert abs(logs["loss"] - float(np.mean(per))) > 1e-6 or \
        wts[0] == wts[1]


def test_packed_parity_vs_padded():
    """Same sequences, packed pack vs padded batch, same weights: the
    token-normalized losses agree within float tolerance (different
    compiled shapes — the XLA batch-shape rule: tolerance, never
    bit-identity)."""
    seqs = _seqs(6, seed=6)
    packed = PackingCollator(T, suggest_rows(
        [len(s[0]) for s in seqs], 6, T, headroom=2.0))(seqs)
    padded = PackingCollator(T, len(seqs), policy="pad")(seqs)
    assert float(packed[4].sum()) == float(padded[4].sum())  # no drops
    model, _ = _packed_model(seed=7)
    la, _ = model.eval_batch(list(packed[:3]), [packed[3]],
                             loss_mask=packed[4])
    lb, _ = model.eval_batch(list(padded[:3]), [padded[3]],
                             loss_mask=padded[4])
    assert abs(float(la) - float(lb)) < 1e-3


def test_predict_packed_no_row_padding():
    """predict() must not row-pad fixed-shape packs (the collator's row
    count is unrelated to the loader's sequences-per-pack batch_size)."""
    seqs = _seqs(10, seed=8)
    coll = PackingCollator(T, 4)
    loader = DataLoader(SeqData(seqs), batch_size=5, shuffle=False,
                        collate_fn=coll)
    model, _ = _packed_model(seed=9)
    tp0 = stat_get("STAT_tail_pad_batches")
    outs = model.predict(loader)
    assert stat_get("STAT_tail_pad_batches") == tp0
    assert np.asarray(outs[0]).shape == (4, T, VOCAB)


def test_token_mask_scalar_loss_raises():
    """Packing REQUIRES a per-token-maskable loss: a loss that only
    yields a scalar must raise, not silently train on pad tokens."""
    seqs = _seqs(6, seed=10)
    batch = PackingCollator(T, 3)(seqs)
    model, net = _packed_model(seed=11)
    model._loss = lambda out, lb: (out.reshape([-1, VOCAB]) ** 2).mean()
    with pytest.raises(TypeError, match="per-token"):
        model.train_batch(list(batch[:3]), [batch[3]],
                          loss_mask=batch[4])


def test_masked_loss_row_mask_still_works():
    """The 1-D row-mask path (tail bucketing) is untouched by the
    token-mask generalization."""
    x = np.random.RandomState(0).randn(8, 4).astype("float32")
    y = np.random.RandomState(1).randint(0, 3, (8,)).astype("int64")
    paddle.seed(12)
    net = nn.Sequential(nn.Linear(4, 3))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(
        0.01, parameters=net.parameters()), nn.CrossEntropyLoss())
    model._dist_ctx = None
    mask = np.ones((8,), "float32")
    mask[6:] = 0.0
    lv, _ = model.eval_batch([x], [y], loss_mask=mask)
    lv_ref, _ = model.eval_batch([x[:6]], [y[:6]])
    np.testing.assert_allclose(float(lv), float(lv_ref), rtol=1e-6)


def test_mp_loader_parent_sees_pack_counters():
    """num_workers>0 runs collate in WORKER processes, whose STAT_ADDs
    land in the worker's registry copy — the generic cross-process stat
    relay (workers ship monitor.drain_deltas() with every batch; the
    parent merges at hand-out) keeps monitoring working."""
    seqs = _seqs(12, seed=20)
    coll = PackingCollator(T, 4)
    loader = DataLoader(SeqData(seqs), batch_size=6, shuffle=False,
                        num_workers=2, collate_fn=coll)
    p0 = stat_get("STAT_packing_packs")
    t0 = stat_get("STAT_packing_tokens")
    s0 = stat_get("STAT_packing_sequences")
    batches = list(loader)
    assert len(batches) == 2
    assert stat_get("STAT_packing_packs") - p0 == 2
    want = sum(int(b[-1].numpy().sum()) for b in batches)
    assert stat_get("STAT_packing_tokens") - t0 == want
    # sequences re-derived from (pos == 0 AND real): one per placement
    seq_want = sum(int(((b[2].numpy() == 0) & (b[-1].numpy() > 0)).sum())
                   for b in batches)
    assert stat_get("STAT_packing_sequences") - s0 == seq_want


# ---------------------------------------------------------------------------
# fleet: packed fit through the sharded step
# ---------------------------------------------------------------------------

def test_sharded_fit_packed(clean_mesh):
    """Packed training through the pjit sharded step: the token mask
    rides as an extra dp-sharded label, one pjit signature for full and
    partial packs, finite loss, carry synced once per epoch. Pack rows
    divide dp so every leaf shards evenly."""
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(13)
    net = PackedLM(max_t=32)
    model = paddle.Model(
        net,
        inputs=[InputSpec([None, 32], "int64", "toks"),
                InputSpec([None, 32], "int32", "seg"),
                InputSpec([None, 32], "int32", "pos")],
        labels=[InputSpec([None, 32], "int64", "labels")])
    opt = fleet.distributed_optimizer(
        paddle.optimizer.Adam(0.01, parameters=net.parameters()))
    model.prepare(opt, nn.CrossEntropyLoss())
    assert model._dist_ctx is not None

    seqs = _seqs(36, seed=14, hi=16)      # short seqs, rows=8 packs
    coll = PackingCollator(32, rows=8)
    loader = DataLoader(SeqData(seqs), batch_size=12, shuffle=False,
                        drop_last=False, collate_fn=coll)
    stat_reset("STAT_sharded_carry_syncs")
    s0 = stat_get("STAT_train_steps")
    model.fit(loader, epochs=2, verbose=0, log_freq=1)
    assert stat_get("STAT_train_steps") == s0 + 6   # 3 packs x 2 epochs
    assert stat_get("STAT_sharded_carry_syncs") == 2
    w = net.head.weight.numpy()
    assert np.isfinite(w).all()
