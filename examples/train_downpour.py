"""Downpour CPU-PS training demo: the DistMultiTrainer/DownpourWorker path.

Workers pull sparse rows per batch from a distributed CPU parameter server,
push merged gradients through a Communicator (async grad aggregation), and
refresh dense params via a background PullDenseWorker — the CPU analog of
the reference's downpour_worker.cc TrainFiles loop over the-one-ps tables.

    python examples/train_downpour.py [--passes 4] [--tcp] [--async-comm]

--tcp brings up a real PS server on 127.0.0.1 and trains over the wire;
the default uses the in-process PsLocalClient (SURVEY §4's two test
mechanisms).
"""

import argparse
import dataclasses
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=4)
    ap.add_argument("--tcp", action="store_true",
                    help="train against a real TCP PS server")
    ap.add_argument("--async-comm", action="store_true",
                    help="asynchronous Communicator sends (default sync)")
    args = ap.parse_args()

    from paddlebox_tpu.config.configs import (SparseOptimizerConfig,
                                              TableConfig, TrainerConfig)
    from paddlebox_tpu.data import BoxDataset, write_synthetic_ctr_files
    from paddlebox_tpu.metrics.auc import BasicAucCalculator
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.models.base import ModelSpec
    from paddlebox_tpu.ps import PSServer, PsLocalClient, TcpPSClient
    from paddlebox_tpu.ps.worker import DownpourTrainer

    data_dir = tempfile.mkdtemp(prefix="pbx_downpour_")
    files, feed = write_synthetic_ctr_files(
        data_dir, num_files=2, lines_per_file=500, num_slots=8,
        vocab_per_slot=300, max_len=3, seed=13)
    feed = dataclasses.replace(feed, batch_size=64)

    D = 4
    table = TableConfig(embedx_dim=D, optimizer=SparseOptimizerConfig(
        mf_create_thresholds=0.0, mf_initial_range=1e-3,
        feature_learning_rate=0.2, mf_learning_rate=0.2))

    server = None
    if args.tcp:
        server = PSServer()
        client = TcpPSClient("127.0.0.1", server.port)
        print(f"TCP PS on 127.0.0.1:{server.port}")
    else:
        client = PsLocalClient()

    tr = DownpourTrainer(
        CtrDnn(ModelSpec(num_slots=8, slot_dim=3 + D), hidden=(32, 16)),
        table, feed, client, TrainerConfig(dense_lr=0.01),
        sync_comm=not args.async_comm)
    tr.metrics.init_metric("auc", "label", "pred", mask_var="mask")

    for i in range(args.passes):
        ds = BoxDataset(feed, read_threads=1)
        ds.set_filelist(files)
        stats = tr.train_pass(ds)
        print(f"pass {i}: loss={stats['loss']:.4f}")

    ds = BoxDataset(feed, read_threads=1)
    ds.set_filelist(files)
    preds, labels = tr.predict_pass(ds)
    calc = BasicAucCalculator(1 << 14)
    calc.add_data(preds, labels)
    calc.compute()
    print(f"eval AUC: {calc.auc():.4f}  rows on PS: "
          f"{client.sparse_size(DownpourTrainer.SPARSE_TABLE)}")
    tr.close()
    if server is not None:
        client.stop_server()
        client.close()


if __name__ == "__main__":
    main()
