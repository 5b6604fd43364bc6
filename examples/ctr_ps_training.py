"""CTR-style training: data_generator → streaming DataFeed → parameter
server.

Raw click logs are authored into the MultiSlot format with
fleet.MultiSlotDataGenerator, streamed through the C++ QueueDataset
(bounded record queue filled by parser threads — host memory stays flat
however large the filelist), and train sparse embeddings held in a
parameter server — the reference's CTR workflow on this framework.

--device_cache: hot vocabulary rows live in TPU HBM
(DeviceEmbeddingCache, the PSGPU/ps_gpu_wrapper.cc analogue): lookups
and optimizer updates for cached rows never leave the device; only the
cold tail rides the PS RPC. Same training semantics (loss-parity is
asserted in tests/test_device_cache.py), zero sparse-table RPCs for hot
traffic.

The deployment the reference's PSGPU targets is a PS across a network and
a local accelerator. Against a loopback host PS the cache saves little: a
device<->host sync on a local v5e is about a millisecond (chip run, PR 23).
Neither form has been timed on today's code.

Run: python examples/ctr_ps_training.py [--device_cache]
"""
import os
import sys

# runnable as `python examples/<name>.py` from anywhere: the repo
# root (one level up) must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import tempfile
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.utils.compile_cache import enable_compile_cache
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.ps import (DeviceEmbeddingCache,
                                       ParameterServer, PsClient)
from paddle_tpu.io import QueueDataset
from paddle_tpu.ops import sequence_ops


class CtrDataGenerator(fleet.MultiSlotDataGenerator):
    """Raw log line "id1 id2 ...,label" → MultiSlot sample (reference:
    fleet data_generator user subclass)."""

    def generate_sample(self, line):
        def gen():
            ids_part, label = line.strip().split(",")
            ids = [int(v) for v in ids_part.split()]
            yield [("ids", ids), ("label", [float(label)])]
        return gen


def write_data(d, files=4, rows=2000, vocab=5000):
    """Author the dataset: raw logs run through the data generator."""
    rng = np.random.RandomState(0)
    paths = []
    for i in range(files):
        raw = []
        for _ in range(rows):
            n = rng.randint(1, 10)
            ids = rng.randint(0, vocab, n)
            raw.append(" ".join(map(str, ids)) + f",{float(ids.sum() % 2)}")
        paths.append(CtrDataGenerator().run_to_file(
            raw, os.path.join(d, f"part-{i}")))
    return paths


def main(device_cache=False):
    enable_compile_cache()
    vocab, dim = 5000, 8
    d = tempfile.mkdtemp()
    paths = write_data(d, vocab=vocab)

    ds = QueueDataset(queue_capacity=2048)   # host memory bound: 2048 recs
    ds.set_use_var([("ids", "int64"), ("label", "float32")])
    ds.set_filelist(paths)
    ds.set_batch_size(512)
    ds.set_thread(4)

    server = ParameterServer(port=0)
    server.add_sparse_table(0, dim=dim, optimizer="adagrad", lr=0.1)
    server.start()
    client = PsClient([server.endpoint])
    cache = None
    if device_cache:
        # hot 80% of the vocabulary HBM-resident; tail stays host-side
        cache = DeviceEmbeddingCache(client, 0, cache_rows=vocab * 4 // 5,
                                     dim=dim, optimizer="adagrad", lr=0.1)

    paddle.seed(0)
    proj = paddle.to_tensor(np.random.randn(dim, 1).astype("float32") * 0.1,
                            stop_gradient=False)
    optim = paddle.optimizer.Adam(1e-2, parameters=[proj])

    t0 = time.perf_counter()
    for epoch in range(3):
        losses = []
        for batch in ds.batches():
            ids, lens = batch["ids"]
            y = batch["label"][0][:, 0]
            uniq, inv = np.unique(ids, return_inverse=True)
            if cache is not None:
                rows = cache.pull(uniq)                  # HBM (+cold RPC)
            else:
                rows = client.pull_sparse(0, uniq)       # PS → host
            table = paddle.to_tensor(rows, stop_gradient=False)
            vecs = paddle.gather(table, paddle.to_tensor(
                inv.reshape(ids.shape)))
            pooled = sequence_ops.sequence_pool(
                vecs, paddle.to_tensor(lens), "mean")
            logit = paddle.matmul(pooled, proj).reshape([-1])
            loss = F.binary_cross_entropy_with_logits(
                logit, paddle.to_tensor(y))
            loss.backward()
            if cache is not None:
                cache.push(uniq, table.grad.numpy())
            else:
                client.push_sparse(0, uniq, np.asarray(table.grad.numpy()))
            optim.step()
            optim.clear_grad()
            losses.append(float(loss.numpy()))
        st = client.stats()[0]
        mode = "device-cache" if cache is not None else "host-ps"
        print(f"epoch {epoch} [{mode}]: loss {np.mean(losses):.4f} "
              f"(PS rows {st['rows']}, pushes {st['push_count']}, "
              f"queue peak {ds.queue_peak_depth()} recs)")
    wall = time.perf_counter() - t0
    if cache is not None:
        cache.flush()  # EndPass: device rows → PS, checkpoints complete
        print(f"done in {wall:.2f}s; device pulls {cache.device_pulls}, "
              f"host pulls {cache.host_pulls} (cold tail only)")
    else:
        print(f"done in {wall:.2f}s; every pull/push was a PS RPC")

    client.stop_server()
    client.close()


if __name__ == "__main__":
    main(device_cache="--device_cache" in sys.argv)
