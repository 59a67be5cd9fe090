#!/usr/bin/env python
"""CRNN captcha OCR (ref role: example/captcha/ +
example/warpctc/lstm_ocr.py — read a variable-length digit string
off an image with conv features -> recurrent sequence model -> CTC,
no per-character segmentation labels).

Synthetic captchas (zero-egress): 24x96 images, 3-5 digits rendered
as distinctive 7-segment-style glyph columns at jittered horizontal
positions over noise.  A small CNN reduces each column band to a
feature vector (width becomes TIME), a BiLSTM reads the band
sequence, CTC aligns it to the digit string.

--quick is the CI gate: greedy-decoded label error rate < 0.15 from
~1.0 untrained (the speech_ctc gate, on a conv front-end instead of
acoustic frames), after 300 steps of batch 32.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

H, W = 24, 96
NDIG = 10
MAX_LAB = 5

# 7-segment styled 8x6 glyphs: each digit lights a distinct subset
_SEGS = {  # (rows, cols) rectangles per segment, on an 8x6 cell
    "top": (slice(0, 2), slice(1, 5)),
    "mid": (slice(3, 5), slice(1, 5)),
    "bot": (slice(6, 8), slice(1, 5)),
    "tl": (slice(0, 4), slice(0, 2)),
    "tr": (slice(0, 4), slice(4, 6)),
    "bl": (slice(4, 8), slice(0, 2)),
    "br": (slice(4, 8), slice(4, 6)),
}
_DIGIT_SEGS = [
    ("top", "bot", "tl", "tr", "bl", "br"),          # 0
    ("tr", "br"),                                    # 1
    ("top", "mid", "bot", "tr", "bl"),               # 2
    ("top", "mid", "bot", "tr", "br"),               # 3
    ("mid", "tl", "tr", "br"),                       # 4
    ("top", "mid", "bot", "tl", "br"),               # 5
    ("top", "mid", "bot", "tl", "bl", "br"),         # 6
    ("top", "tr", "br"),                             # 7
    ("top", "mid", "bot", "tl", "tr", "bl", "br"),   # 8
    ("top", "mid", "bot", "tl", "tr", "br"),         # 9
]


def _glyph(d):
    g = np.zeros((8, 6), np.float32)
    for s in _DIGIT_SEGS[d]:
        g[_SEGS[s]] = 1.0
    return g


_GLYPHS = [_glyph(d) for d in range(NDIG)]


def make_captchas(rs, n):
    x = rs.rand(n, 1, H, W).astype(np.float32) * 0.25
    y = np.full((n, MAX_LAB), -1, np.float32)
    yl = np.zeros(n, np.float32)
    for i in range(n):
        L = rs.randint(3, MAX_LAB + 1)
        digs = rs.randint(0, NDIG, L)
        cx = rs.randint(2, 8)
        for d in digs:
            gy = rs.randint(6, 10)
            scale = rs.uniform(0.85, 1.0)
            x[i, 0, gy:gy + 8, cx:cx + 6] += _GLYPHS[d] * scale
            cx += rs.randint(14, 18)
        y[i, :L] = digs
        yl[i] = L
    return np.clip(x, 0, 1), y, yl


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="CRNN captcha OCR")
    p.add_argument("--hidden", type=int, default=48)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--clip", type=float, default=1.0)
    p.add_argument("--quick", action="store_true")
    return p.parse_args(argv)


from common import edit_distance  # noqa: E402


def greedy_decode(logits):
    path = logits.argmax(1)
    out, prev = [], -1
    for p in path:
        if p != prev and p != NDIG:      # blank = last channel
            out.append(int(p))
        prev = p
    return out


def main(argv=None):
    from incubator_mxnet_tpu.utils.platform import maybe_force_cpu
    maybe_force_cpu()
    args = parse_args(argv)
    if args.quick:
        args.steps = 300

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, nd
    from incubator_mxnet_tpu.gluon import nn, rnn, utils as gutils

    class CRNN(gluon.Block):
        """Conv band encoder -> BiLSTM -> per-column digit logits."""

        def __init__(self, hidden, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.conv1 = nn.Conv2D(12, 3, padding=1,
                                       activation="relu")
                self.pool1 = nn.MaxPool2D((2, 2))      # 12x48
                self.conv2 = nn.Conv2D(24, 3, padding=1,
                                       activation="relu")
                self.pool2 = nn.MaxPool2D((2, 2))      # 6x24
                self.lstm = rnn.LSTM(hidden, num_layers=1,
                                     bidirectional=True,
                                     layout="NTC",
                                     input_size=24 * 6)
                self.proj = nn.Dense(NDIG + 1, flatten=False)

        def forward(self, x):
            f = self.pool2(self.conv2(self.pool1(self.conv1(x))))
            # (N, C, H', W') -> time = W': (N, W', C*H')
            f = f.transpose((0, 3, 1, 2)).reshape((0, 24, -1))
            h, _ = self.lstm(f, self.lstm.begin_state(x.shape[0]))
            return self.proj(h)                        # (N, T, 11)

    mx.random.seed(0)
    rs = np.random.RandomState(0)
    net = CRNN(args.hidden)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    ctc = gluon.loss.CTCLoss(layout="NTC", label_layout="NT")

    def ler(n_eval=64):
        X, Y, yl = make_captchas(np.random.RandomState(1), n_eval)
        logits = net(nd.array(X)).asnumpy()
        errs = tot = 0
        for i in range(n_eval):
            hyp = greedy_decode(logits[i])
            ref = [int(c) for c in Y[i][:int(yl[i])]]
            errs += edit_distance(hyp, ref)
            tot += len(ref)
        return errs / tot

    init_ler = ler()
    first = last = None
    T = 24   # post-conv sequence length
    for it in range(args.steps):
        X, Y, yl = make_captchas(rs, args.batch_size)
        xb, yb = nd.array(X), nd.array(Y)
        xlb = nd.array(np.full(args.batch_size, T, np.float32))
        ylb = nd.array(yl)
        with autograd.record():
            loss = ctc(net(xb), yb, xlb, ylb).mean()
        loss.backward()
        gutils.clip_global_norm(
            [p.grad() for p in net.collect_params().values()
             if p.grad_req != "null"], args.clip)
        trainer.step(args.batch_size)
        l = float(loss.asnumpy())
        if first is None:
            first = l
        last = l
        if it % 50 == 0:
            print(f"step {it}: ctc_loss={l:.3f} "
                  f"ler={ler(32):.3f}", flush=True)

    final_ler = ler()
    summary = dict(first_loss=first, final_loss=last,
                   init_ler=float(init_ler),
                   final_ler=float(final_ler))
    print(json.dumps(summary))
    if args.quick:
        assert final_ler < 0.15, summary
        assert last < 0.3 * first, summary
    return summary


if __name__ == "__main__":
    main()
