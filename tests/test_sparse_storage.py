"""Real sparse storage: no O(full-shape) buffer
on construction or through the sparse kernel/kvstore paths, plus the
LibSVM linear-classification convergence gate (driver config 5,
ref: example/sparse/linear_classification.py).
"""
import os

import numpy as np

import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ndarray import sparse


def test_row_sparse_no_dense_buffer_at_scale():
    """(100000, 128) row-sparse with 5 rows must allocate O(k), not
    O(vocab) — the round-2 verdict's memory gate."""
    k, vocab, dim = 5, 100_000, 128
    rows = np.arange(0, k * 7, 7, dtype=np.int64)
    vals = np.random.RandomState(0).rand(k, dim).astype(np.float32)
    arr = sparse.row_sparse_array((vals, rows), shape=(vocab, dim))
    assert not arr.has_dense_mirror()
    assert arr.shape == (vocab, dim)
    assert arr.data.shape == (k, dim)
    assert arr.indices.shape == (k,)
    # sparse kernels keep it sparse
    kept = sparse.retain(arr, mx.nd.array([0, 7, 9], dtype="int64"))
    assert not arr.has_dense_mirror()
    assert not kept.has_dense_mirror()
    np.testing.assert_allclose(np.asarray(kept.data._data)[0], vals[0])
    np.testing.assert_allclose(np.asarray(kept.data._data)[2], 0.0)
    both = sparse.elemwise_add(arr, kept)
    assert not both.has_dense_mirror()
    # densify only on explicit request
    dense = arr.tostype("default")
    assert dense.shape == (vocab, dim)


def test_csr_no_dense_buffer_and_dot():
    rs = np.random.RandomState(1)
    dense = np.zeros((6, 50_000), np.float32)
    cols = rs.randint(0, 50_000, 30)
    dense[rs.randint(0, 6, 30), cols] = rs.rand(30)
    csr = sparse.csr_matrix(dense)
    assert not csr.has_dense_mirror()
    w = mx.nd.array(rs.rand(50_000, 4).astype(np.float32))
    out = sparse.dot(csr, w)
    assert not csr.has_dense_mirror()
    np.testing.assert_allclose(np.asarray(out._data), dense @
                               np.asarray(w._data), rtol=1e-4,
                               atol=1e-5)


def test_csr_T_dot_row_sparse_output():
    """dot(csr.T, dense, forward_stype='row_sparse') returns only the
    touched columns (the embedding-grad path)."""
    rs = np.random.RandomState(2)
    dense = np.zeros((4, 1000), np.float32)
    dense[0, 5] = 1.0
    dense[1, 5] = 2.0
    dense[2, 700] = 3.0
    csr = sparse.csr_matrix(dense)
    d = rs.rand(4, 3).astype(np.float32)
    out = sparse.dot(csr, mx.nd.array(d), transpose_a=True,
                     forward_stype="row_sparse")
    assert isinstance(out, sparse.RowSparseNDArray)
    assert not out.has_dense_mirror()
    got_idx = np.asarray(out.indices._data)
    np.testing.assert_array_equal(got_idx, [5, 700])
    want = dense.T @ d
    np.testing.assert_allclose(np.asarray(out.data._data),
                               want[[5, 700]], rtol=1e-5)


def test_kvstore_row_sparse_pull_sparse_out():
    vocab, dim = 10_000, 16
    kv = mx.kvstore.create("local")
    w = mx.nd.array(np.random.RandomState(3).rand(vocab, dim)
                    .astype(np.float32))
    kv.init("emb", w)
    out = sparse.zeros("row_sparse", (vocab, dim))
    rids = mx.nd.array([3, 8, 42], dtype="int64")
    kv.row_sparse_pull("emb", out=out, row_ids=rids)
    assert not out.has_dense_mirror()
    assert out.data.shape == (3, dim)
    np.testing.assert_allclose(np.asarray(out.data._data),
                               np.asarray(w._data)[[3, 8, 42]])


def _write_libsvm(path, X, y):
    with open(path, "w") as f:
        for row, label in zip(X, y):
            items = [str(float(label))]
            for j in np.nonzero(row)[0]:
                items.append(f"{j}:{row[j]:.6f}")
            f.write(" ".join(items) + "\n")


def test_libsvm_linear_classification_converges(tmp_path):
    """Driver config 5: sparse logistic regression on LibSVM data
    (ref: example/sparse/linear_classification.py) — CSR batches,
    sparse dot forward, row-sparse gradient, lazy sgd update."""
    rs = np.random.RandomState(4)
    n, d = 512, 2000
    X = np.zeros((n, d), np.float32)
    for i in range(n):
        nz = rs.choice(d, 20, replace=False)
        X[i, nz] = rs.rand(20)
    w_true = np.zeros(d, np.float32)
    w_true[rs.choice(d, 100, replace=False)] = \
        rs.randn(100).astype(np.float32) * 2
    y = (X @ w_true > 0).astype(np.float32)
    path = tmp_path / "train.libsvm"
    _write_libsvm(path, X, y)

    batch = 64
    it = mx.io.LibSVMIter(data_libsvm=str(path), data_shape=(d,),
                          batch_size=batch)
    weight = mx.nd.array(np.zeros((d, 1), np.float32))
    losses = []
    for epoch in range(15):
        it.reset()
        total, nb = 0.0, 0
        for b in it:
            csr = b.data[0]
            assert isinstance(csr, sparse.CSRNDArray)
            label = b.label[0]._data.reshape(-1, 1)
            logits = sparse.dot(csr, weight)._data
            p = 1.0 / (1.0 + jnp.exp(-logits))
            eps = 1e-7
            loss = -jnp.mean(label * jnp.log(p + eps)
                             + (1 - label) * jnp.log(1 - p + eps))
            dlogits = (p - label) / label.shape[0]
            grad = sparse.dot(csr, mx.nd.NDArray(dlogits),
                              transpose_a=True,
                              forward_stype="row_sparse")
            assert isinstance(grad, sparse.RowSparseNDArray)
            sparse.sgd_update(mx.nd.NDArray(weight._data), grad,
                              lr=5.0, out=weight)
            total += float(loss)
            nb += 1
        losses.append(total / nb)
    assert losses[-1] < 0.35 * losses[0], losses
    acc = float(np.mean(
        ((X @ np.asarray(weight._data).ravel()) > 0) == y))
    assert acc > 0.9, acc


def test_retain_empty_and_duplicate_semantics():
    """Regression (round-3 review): retain on an empty array returns
    zero rows; duplicate stored indices sum (scatter-add semantics)."""
    z = sparse.zeros("row_sparse", (5, 2))
    kept = sparse.retain(z, mx.nd.array([0, 3], dtype="int64"))
    np.testing.assert_allclose(np.asarray(kept.data._data),
                               np.zeros((2, 2)))
    g = sparse.row_sparse_array(
        (np.array([[1.0], [3.0]], np.float32),
         np.array([2, 2], np.int64)), shape=(5, 1))
    kept = sparse.retain(g, mx.nd.array([2], dtype="int64"))
    np.testing.assert_allclose(np.asarray(kept.data._data), [[4.0]])
    # unsorted want order is preserved
    g2 = sparse.row_sparse_array(
        (np.array([[1.0], [2.0]], np.float32),
         np.array([1, 3], np.int64)), shape=(5, 1))
    kept = sparse.retain(g2, mx.nd.array([3, 1], dtype="int64"))
    np.testing.assert_array_equal(np.asarray(kept.indices._data),
                                  [3, 1])
    np.testing.assert_allclose(np.asarray(kept.data._data),
                               [[2.0], [1.0]])


def test_row_sparse_pull_duplicate_row_ids():
    """Regression (round-3 review): duplicated row_ids must not
    double the pulled rows on densify."""
    kv = mx.kvstore.create("local")
    w = mx.nd.array(np.arange(8, dtype=np.float32).reshape(4, 2))
    kv.init("w", w)
    out = sparse.zeros("row_sparse", (4, 2))
    kv.row_sparse_pull("w", out=out,
                       row_ids=mx.nd.array([1, 1, 3], dtype="int64"))
    dense = np.asarray(out._data)
    np.testing.assert_allclose(dense[1], [2.0, 3.0])
    np.testing.assert_allclose(dense[3], [6.0, 7.0])
