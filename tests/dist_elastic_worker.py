"""Elastic training worker: checkpoint every epoch, crash rank 1
mid-train on the first attempt, resume from the newest checkpoint
after tools/launch.py --max-restarts relaunches the job (the
reference's scheduler-restart failure model; SURVEY §5 failure
detection).  Spawned by tests/test_dist_launch.py — not a pytest
module."""
import glob
import os
import signal
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import incubator_mxnet_tpu as mx

TOTAL_EPOCHS = 14
CRASH_AFTER_EPOCH = 2      # rank 1 dies once this epoch is saved


def main():
    ckdir = os.environ["MXTPU_ELASTIC_DIR"]
    attempt = int(os.environ.get("MXTPU_RESTART_ATTEMPT", "0"))
    kv = mx.kvstore.create("dist_sync")
    # jax.distributed takes SIGTERM for its preemption notice; a rank
    # left in a collective with a dead peer then sits out the
    # launcher's 10 s of grace before the kill.  That wait is not what
    # this worker is here to show: die at the launcher's first signal.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    r = kv.rank
    prefix = os.path.join(ckdir, "model")

    # learnable synthetic problem, data sharded by rank
    rs = np.random.RandomState(0)
    x = rs.rand(256, 10).astype(np.float32)
    w = rs.rand(10, 5).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.float32)
    it = mx.io.NDArrayIter(x[r::2], y[r::2], batch_size=16,
                           label_name="softmax_label")

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, name="fc1", num_hidden=32)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, name="fc2", num_hidden=5)
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    # resume: newest checkpoint wins (both ranks read shared disk)
    begin, arg_params, aux_params = 0, None, None
    saved = glob.glob(prefix + "-*.params")
    if saved:
        begin = max(int(p.rsplit("-", 1)[1].split(".")[0])
                    for p in saved)
        _, arg_params, aux_params = mx.model.load_checkpoint(
            prefix, begin)
        print(f"RESUMED_FROM {begin} rank {r}", flush=True)

    def epoch_cb(epoch, symbol, arg_p, aux_p):
        if r == 0:
            mx.model.save_checkpoint(prefix, epoch + 1, symbol,
                                     arg_p, aux_p)
        kv.barrier()          # checkpoint visible to all ranks
        if attempt == 0 and r == 1 and epoch + 1 == CRASH_AFTER_EPOCH:
            print(f"CRASHING rank {r} after epoch {epoch}",
                  flush=True)
            os._exit(7)       # hard death, no teardown

    mx.random.seed(42)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, begin_epoch=begin, num_epoch=TOTAL_EPOCHS,
            kvstore=kv, optimizer="sgd",
            optimizer_params=dict(learning_rate=1.0),
            initializer=mx.initializer.Xavier(),
            arg_params=arg_params, aux_params=aux_params,
            epoch_end_callback=epoch_cb)

    acc = mod.score(it, "acc")[0][1]
    assert acc > 0.88, f"rank {r} did not converge: acc={acc}"
    print(f"ELASTIC_OK rank {r} attempt {attempt} acc {acc:.3f}",
          flush=True)


if __name__ == "__main__":
    main()
