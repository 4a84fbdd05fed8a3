#!/usr/bin/env python
"""Bucketing LSTM training (reference ``example/rnn/bucketing/`` [path
cite — unverified]): variable-length sequences batched into length
buckets, one shape-specialized compiled program per bucket, ALL buckets
sharing one parameter set via ``BucketingModule``.

Task (solvable by construction, exercises real recurrence): the LABEL
is whether the marker token ever appears in the (variable-length,
padded) sequence — the LSTM must latch the sighting and carry it to
the final step. Accuracy well above chance after a few epochs is
asserted.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(__file__), "..", "..", ".."))

import numpy as np

BUCKETS = (8, 12, 16)
VOCAB, NUM_CLS, HIDDEN, EMBED = 8, 2, 32, 16
MARKER = 1      # label = does this token appear anywhere?
BATCH = 32      # sym_gen closes over it (state shape needs B)


class BucketIter:
    """Minimal bucketed iterator (the reference's BucketSentenceIter
    shape): group sequences by smallest fitting bucket, pad to the
    bucket length, emit DataBatch with ``bucket_key``."""

    def __init__(self, seqs, labels, batch_size):
        from mxtpu.io import DataDesc
        self.batch_size = batch_size
        self._ddesc = {b: [DataDesc("data", (batch_size, b))]
                       for b in BUCKETS}
        self._ldesc = [DataDesc("softmax_label", (batch_size,))]
        self._by_bucket = {b: [] for b in BUCKETS}
        for s, y in zip(seqs, labels):
            b = next(bk for bk in BUCKETS if len(s) <= bk)
            padded = np.zeros(b, np.int32)
            padded[:len(s)] = s
            self._by_bucket[b].append((padded, y))
        self.reset()

    def reset(self):
        self._plan = []
        for b, rows in self._by_bucket.items():
            for i in range(0, len(rows) - self.batch_size + 1,
                           self.batch_size):
                self._plan.append((b, rows[i:i + self.batch_size]))
        np.random.default_rng(0).shuffle(self._plan)
        self._i = 0

    @property
    def provide_data(self):
        return self._ddesc[BUCKETS[-1]]

    @property
    def provide_label(self):
        return self._ldesc

    def __iter__(self):
        return self

    def __next__(self):
        import mxtpu as mx
        from mxtpu.io import DataBatch
        if self._i >= len(self._plan):
            raise StopIteration
        b, rows = self._plan[self._i]
        self._i += 1
        data = np.stack([r[0] for r in rows])
        label = np.array([r[1] for r in rows], np.float32)
        return DataBatch(data=[mx.nd.array(data)],
                         label=[mx.nd.array(label)], bucket_key=b,
                         provide_data=self._ddesc[b],
                         provide_label=self._ldesc)


def make_data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    seqs, labels = [], []
    for i in range(n):
        ln = int(rng.integers(4, BUCKETS[-1] + 1))
        s = rng.integers(2, VOCAB, ln)       # marker-free base
        if i % 2 == 0:                       # balanced classes
            s[rng.integers(0, ln)] = MARKER
        seqs.append(s)
        labels.append(int(MARKER in s))
    return seqs, labels


def sym_gen(seq_len):
    """One bucket's symbol: embed → fused LSTM → last output → FC →
    softmax. Parameter NAMES are bucket-independent, so
    BucketingModule shares one weight set across every bucket."""
    from mxtpu import sym
    from mxtpu.ndarray.ops import rnn_param_layout
    data = sym.var("data")
    emb = sym.Embedding(data, input_dim=VOCAB, output_dim=EMBED,
                        name="embed")
    tnc = sym.transpose(emb, axes=(1, 0, 2))         # (T, B, E)
    _, total = rnn_param_layout("lstm", EMBED, HIDDEN, 1, False)
    rnn_params = sym.var("lstm_parameters", shape=(total,))
    # learned initial state (bucket-independent shape; the batch dim
    # is fixed by the iterator)
    h0 = sym.var("lstm_h0", shape=(1, BATCH, HIDDEN))
    c0 = sym.var("lstm_c0", shape=(1, BATCH, HIDDEN))
    out = sym.RNN(tnc, rnn_params, h0, state_cell=c0,
                  state_size=HIDDEN, num_layers=1, mode="lstm",
                  name="lstm")
    last = sym.SequenceLast(out)                      # (B, H)
    fc = sym.FullyConnected(last, num_hidden=NUM_CLS, name="cls")
    return sym.SoftmaxOutput(fc, name="softmax"), ("data",), \
        ("softmax_label",)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=6)
    args = p.parse_args()
    import mxtpu as mx

    seqs, labels = make_data()
    it = BucketIter(seqs, labels, BATCH)
    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=BUCKETS[-1],
                                 context=mx.cpu())
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="adam",
                       optimizer_params=(("learning_rate", 0.01),))
    metric = mx.metric.Accuracy()
    for epoch in range(args.epochs):
        it.reset()
        metric.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.update_metric(metric, batch.label)
            mod.backward()
            mod.update()
        print(f"epoch {epoch}: {metric.get()[0]} "
              f"{metric.get()[1]:.3f}", flush=True)
    name, acc = metric.get()
    buckets_used = sorted(mod._buckets)
    print(f"buckets compiled: {buckets_used}, final {name}: {acc:.3f}")
    assert len(buckets_used) == len(BUCKETS), "not all buckets hit"
    assert acc > 0.9, f"LSTM failed to learn first-token recall ({acc})"
    print("bucketing rnn example OK")


if __name__ == "__main__":
    main()
