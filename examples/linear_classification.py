"""Sparse linear classification (driver config 5; ref:
example/sparse/linear_classification.py:109-124).

Criteo-style workload: logistic regression over high-dimensional
sparse features.  The three sparse mechanisms the reference example
exists to demonstrate are all exercised end-to-end:

  - **LibSVM input** -> CSR batches (`mx.io.LibSVMIter`; ref:
    src/io/iter_libsvm.cc:200)
  - **row_sparse weight through KVStore**: the full (dim, 1) weight
    lives in the store; every batch pulls ONLY the rows its features
    touch via ``kv.row_sparse_pull`` (ref: kvstore.py:289) and pushes
    a row-sparse gradient back
  - **lazy update store-side**: the updater applies
    ``sparse.sgd_update`` so untouched rows are never read or
    written (ref: optimizer_op.cc sparse sgd alias)

TPU note: the O(nnz) gather/segment-sum kernels behind `sparse.dot`
are XLA ops, so the same script runs on the chip; the sparse pull
keeps host<->device traffic at O(touched rows), which is the entire
point of the reference flow on a parameter server too.

Every row carries the same number of features (as Criteo's rows carry
the same fields), so the CSR arrays of every batch have one shape and
the operators over them compile once; what still changes from batch
to batch is the number of distinct rows touched.  --quick: 2048 rows
of dimension 400 with 10 features each, batch 32, 15 epochs.

Run: python examples/linear_classification.py [--quick]
"""
import argparse
import json
import os
import tempfile
import time

import numpy as np


def make_libsvm(path, n, dim, density, rs, true_w, noise=0.05):
    """Synthetic separable-ish problem in LibSVM text format."""
    with open(path, "w") as f:
        for _ in range(n):
            nnz = max(1, round(dim * density))
            cols = np.sort(rs.choice(dim, size=nnz, replace=False))
            vals = rs.rand(nnz).astype(np.float32) + 0.1
            margin = float(np.dot(vals, true_w[cols]))
            y = 1.0 if margin + noise * rs.randn() > 0 else 0.0
            toks = " ".join(f"{c}:{v:.4f}" for c, v in zip(cols, vals))
            f.write(f"{y} {toks}\n")


def evaluate(batches, kv, dim, bias, mx, nd, sparse):
    """NLL + accuracy with the CURRENT store weight, fetched through
    the public ``kv.pull`` (the reference's pull-all-rows-before-
    checkpoint pattern, linear_classification.py:122-124)."""
    weight = nd.zeros((dim, 1))
    kv.pull("weight", out=weight)
    nll = correct = total = 0.0
    for b in batches:
        x, y = b.data[0], b.label[0].asnumpy().ravel()
        logits = sparse.dot(x, weight).asnumpy()[:, 0] + bias
        p = 1.0 / (1.0 + np.exp(-logits))
        p = np.clip(p, 1e-8, 1 - 1e-8)
        nll += float(-(y * np.log(p)
                       + (1 - y) * np.log(1 - p)).sum())
        correct += float(((p > 0.5) == (y > 0.5)).sum())
        total += len(y)
    return nll / total, correct / total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--num-epochs", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3.0)
    ap.add_argument("--kv-store", default="local")
    args = ap.parse_args(argv)

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.ndarray import sparse

    dim = args.dim or (400 if args.quick else 2000)
    n_train = 2048 if args.quick else 8192
    epochs = args.num_epochs or (15 if args.quick else 30)
    batch_size = args.batch_size or (32 if args.quick else 64)

    rs = np.random.RandomState(3)
    true_w = (rs.randn(dim) * 2).astype(np.float32)
    t0 = time.time()
    with tempfile.TemporaryDirectory() as td:
        tr_path = os.path.join(td, "train.libsvm")
        va_path = os.path.join(td, "val.libsvm")
        density = 0.025 if args.quick else 0.02
        make_libsvm(tr_path, n_train, dim, density, rs, true_w)
        make_libsvm(va_path, max(256, n_train // 8), dim, density,
                    rs, true_w)
        train_it = mx.io.LibSVMIter(data_libsvm=tr_path,
                                    data_shape=(dim,),
                                    batch_size=batch_size)
        val_it = mx.io.LibSVMIter(data_libsvm=va_path,
                                  data_shape=(dim,),
                                  batch_size=batch_size)
        train_batches = list(train_it)
        val_batches = list(val_it)

    # weight lives in the KVStore; updates are lazy row-sparse SGD
    # applied store-side (the reference's server-side updater role)
    kv = mx.kv.create(args.kv_store)
    kv.init("weight", nd.zeros((dim, 1)))
    kv.set_updater(
        lambda key, grad, stored: sparse.sgd_update(
            stored, grad, lr=args.lr))
    w_rsp = sparse.row_sparse_array(np.zeros((1, 1), np.float32),
                                    shape=(dim, 1))
    bias = 0.0

    # untrained baseline (zero weight -> nll = ln 2): the gate
    # measures training progress from here
    first_nll, _ = evaluate(val_batches, kv, dim, bias, mx, nd,
                            sparse)
    for epoch in range(epochs):
        pulled_rows = 0
        for b in train_batches:
            x, y = b.data[0], b.label[0].asnumpy().ravel()
            # O(touched rows) pull — the heart of the example
            rid = x.indices
            kv.row_sparse_pull("weight", out=w_rsp, row_ids=rid)
            pulled_rows += int(w_rsp.indices.shape[0])
            logits = sparse.dot(x, w_rsp).asnumpy()[:, 0] + bias
            p = 1.0 / (1.0 + np.exp(-logits))
            gl = nd.array(((p - y) / len(y))[:, None]
                          .astype(np.float32))
            gw = sparse.dot(x, gl, transpose_a=True,
                            forward_stype="row_sparse")
            kv.push("weight", gw)               # lazy update inside
            bias -= args.lr * float((p - y).mean())
    final_nll, final_acc = evaluate(val_batches, kv, dim, bias,
                                    mx, nd, sparse)

    dense_rows_equiv = len(train_batches) * dim
    out = {"example": "linear_classification", "dim": dim,
           "epochs": epochs, "first_nll": round(first_nll, 4),
           "final_nll": round(final_nll, 4),
           "val_acc": round(final_acc, 4),
           "rows_pulled_per_epoch": pulled_rows,
           "dense_rows_equiv_per_epoch": dense_rows_equiv,
           "pull_savings": round(1 - pulled_rows / dense_rows_equiv,
                                 4),
           "seconds": round(time.time() - t0, 1)}
    print(json.dumps(out))
    if args.quick:
        # 2048 rows read 0.89-0.91 on four seeds; at 1024 rows the
        # ceiling was ~0.85-0.88 (a dense full-batch GD oracle reached
        # 0.88): gate at 0.8
        assert final_nll < 0.65 * first_nll, (first_nll, final_nll)
        assert final_acc > 0.8, final_acc
        assert pulled_rows < 0.75 * dense_rows_equiv, \
            (pulled_rows, dense_rows_equiv)
    return out


if __name__ == "__main__":
    main()
