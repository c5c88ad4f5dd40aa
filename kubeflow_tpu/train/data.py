"""In-memory datasets + batch iterator.

Offline environment: no downloads. Real data = sklearn digits (8x8 grayscale
digits, 1797 samples — the honest offline MNIST stand-in; an MLP reaches >97%
test accuracy, matching BASELINE.md config #1's pass criterion). Synthetic
generators provide MNIST-/ImageNet-/BERT-shaped batches for throughput
benchmarks where content doesn't matter.

TPU notes: batches are host numpy, converted to device arrays at the jit
boundary; shapes are static per epoch (remainder batches dropped) so XLA
compiles once.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Iterator

import numpy as np


@dataclass
class Dataset:
    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    num_classes: int

    @property
    def input_shape(self) -> tuple[int, ...]:
        return self.x_train.shape[1:]


def load_digits_dataset(test_fraction: float = 0.2, seed: int = 0) -> Dataset:
    """sklearn digits, normalized to [0,1], deterministic split."""
    from sklearn.datasets import load_digits

    d = load_digits()
    x = (d.data / 16.0).astype(np.float32)
    y = d.target.astype(np.int32)
    rng = np.random.RandomState(seed)
    perm = rng.permutation(len(x))
    x, y = x[perm], y[perm]
    n_test = int(len(x) * test_fraction)
    return Dataset(
        x_train=x[n_test:], y_train=y[n_test:],
        x_test=x[:n_test], y_test=y[:n_test],
        num_classes=10,
    )


def synthetic_image_dataset(
    n_train: int = 1024,
    n_test: int = 256,
    shape: tuple[int, ...] = (28, 28, 1),
    num_classes: int = 10,
    seed: int = 0,
) -> Dataset:
    """Procedural image classification set with learnable class structure:
    each class is a fixed random template + noise, so accuracy is meaningful."""
    rng = np.random.RandomState(seed)
    templates = rng.normal(0, 1, size=(num_classes, *shape)).astype(np.float32)

    def make(n: int) -> tuple[np.ndarray, np.ndarray]:
        y = rng.randint(0, num_classes, size=n).astype(np.int32)
        x = templates[y] + rng.normal(0, 0.5, size=(n, *shape)).astype(np.float32)
        return x.astype(np.float32), y

    x_tr, y_tr = make(n_train)
    x_te, y_te = make(n_test)
    return Dataset(x_tr, y_tr, x_te, y_te, num_classes)


def synthetic_text_dataset(
    n_train: int = 1024,
    n_test: int = 256,
    seq_len: int = 128,
    vocab_size: int = 1024,
    num_classes: int = 2,
    pad_token_id: int = 0,
    seed: int = 0,
) -> Dataset:
    """Token-sequence classification set with learnable class structure:
    each class draws tokens from its own skewed unigram distribution, with
    random-length tail padding so padding masks are exercised."""
    rng = np.random.RandomState(seed)
    # class-specific token distributions over [1, vocab) (0 reserved for pad)
    logits = rng.normal(0, 1.5, size=(num_classes, vocab_size - 1))
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)

    def make(n: int) -> tuple[np.ndarray, np.ndarray]:
        y = rng.randint(0, num_classes, size=n).astype(np.int32)
        x = np.zeros((n, seq_len), np.int32)
        for i in range(n):
            length = rng.randint(seq_len // 2, seq_len + 1)
            x[i, :length] = rng.choice(
                vocab_size - 1, size=length, p=probs[y[i]]
            ) + 1
        x[:, :] = np.where(x == 0, pad_token_id, x)
        return x, y

    x_tr, y_tr = make(n_train)
    x_te, y_te = make(n_test)
    return Dataset(x_tr, y_tr, x_te, y_te, num_classes)


def synthetic_lm_dataset(
    n_train: int = 512,
    n_test: int = 128,
    seq_len: int = 128,
    vocab_size: int = 512,
    seed: int = 0,
    noise: float = 0.1,
) -> Dataset:
    """Causal-LM set with learnable structure: a noisy affine token chain
    (next = (a·tok + b) mod (V-1) + 1), so next-token loss is reducible.
    Labels ARE the inputs — models.gpt.causal_lm_loss shifts internally."""
    rng = np.random.RandomState(seed)
    a, b = 31, 17  # coprime with vocab-1 keeps the chain full-period-ish

    def make(n: int) -> tuple[np.ndarray, np.ndarray]:
        x = np.zeros((n, seq_len), np.int32)
        x[:, 0] = rng.randint(1, vocab_size, size=n)
        for t in range(1, seq_len):
            nxt = (x[:, t - 1] * a + b) % (vocab_size - 1) + 1
            flip = rng.rand(n) < noise
            nxt[flip] = rng.randint(1, vocab_size, size=flip.sum())
            x[:, t] = nxt
        return x, x.copy()

    x_tr, y_tr = make(n_train)
    x_te, y_te = make(n_test)
    return Dataset(x_tr, y_tr, x_te, y_te, num_classes=vocab_size)


# positions excluded from token-level objectives (HF convention); the single
# source of truth — models.bert imports it
IGNORE_LABEL = -100


def mask_tokens_for_mlm(
    x: np.ndarray,
    vocab_size: int,
    mask_token_id: int,
    mask_prob: float = 0.15,
    pad_token_id: int = 0,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """BERT MLM corruption: of the selected positions, 80% become [MASK],
    10% a random token drawn from [1, vocab_size), 10% unchanged; labels
    carry the ORIGINAL ids at selected positions and IGNORE_LABEL elsewhere.
    Pass the DATA vocab (excluding the mask id) as vocab_size so random
    replacements never draw the sentinel."""
    rng = np.random.RandomState(seed)
    labels = np.full_like(x, IGNORE_LABEL)
    corrupted = x.copy()
    selectable = x != pad_token_id
    selected = (rng.rand(*x.shape) < mask_prob) & selectable
    labels[selected] = x[selected]
    roll = rng.rand(*x.shape)
    corrupted[selected & (roll < 0.8)] = mask_token_id
    rand_repl = selected & (roll >= 0.8) & (roll < 0.9)
    random_ids = rng.randint(1, vocab_size, size=x.shape)
    corrupted[rand_repl] = random_ids[rand_repl]
    return corrupted, labels


def batches(
    x: np.ndarray,
    y: np.ndarray,
    batch_size: int,
    *,
    seed: int | None = None,
    drop_remainder: bool = True,
) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """One epoch of (x, y) minibatches; static shapes when drop_remainder."""
    n = len(x)
    idx = np.arange(n)
    if seed is not None:
        np.random.RandomState(seed).shuffle(idx)
    stop = (n // batch_size) * batch_size if drop_remainder else n
    for i in range(0, stop, batch_size):
        sl = idx[i : i + batch_size]
        yield x[sl], y[sl]


def steps_per_epoch(n: int, batch_size: int) -> int:
    return n // batch_size


def prefetch_to_device(
    it: Iterator, mesh, size: int = 2, process_local: bool = False
) -> Iterator:
    """Double-buffering host->device prefetch.

    jax.device_put is asynchronous: enqueueing the NEXT batch's transfer
    before blocking on the current step overlaps PCIe/HBM copy with compute,
    keeping input transfer off the step critical path (VERDICT.md round-1
    weak #8). `size=2` is classic double buffering; more buys nothing once
    transfer < step time.
    """
    from collections import deque

    import jax

    from kubeflow_tpu.parallel.sharding import shard_batch
    buf: deque = deque()
    with jax.set_mesh(mesh):
        for b in it:
            buf.append(shard_batch(b, mesh, process_local=process_local))
            if len(buf) >= size:
                yield buf.popleft()
        while buf:
            yield buf.popleft()


# ----------------------------------------------------------- async host load

#: process-global loader accounting — the kftpu_train_loader_* /metrics
#: families (observability.py reads the snapshot; trainers/drills construct
#: loaders ad hoc, so a registry is the only stable aggregation point)
_LOADER_MU = threading.Lock()
_LOADER_METRICS = {
    "batches_total": 0,          # batches handed to a consumer
    "queue_wait_seconds_total": 0.0,   # consumer time blocked on the queue
    "assemble_seconds_total": 0.0,     # producer-thread host work (overlapped)
    "errors_total": 0,           # loader-thread exceptions re-raised
    "threads_started_total": 0,
}
_LIVE_LOADERS = 0


def loader_metrics_snapshot() -> dict:
    with _LOADER_MU:
        return dict(_LOADER_METRICS, live_loaders=_LIVE_LOADERS)


def reset_loader_metrics() -> None:
    """Test hook: zero the counters (live_loaders is recomputed live)."""
    with _LOADER_MU:
        for k in _LOADER_METRICS:
            _LOADER_METRICS[k] = 0 if isinstance(
                _LOADER_METRICS[k], int) else 0.0


class _LoaderStop(Exception):
    """Internal: consumer closed while the producer was blocked."""


class AsyncLoader:
    """Background-thread host input pipeline: batch assembly + host
    sharding off the step critical path (ROADMAP item 5; the MLPerf
    async-input-pipeline move of 1909.09756).

    Pulls items from `src` on a worker thread, applies `transform` (the
    expensive host work — e.g. ``shard_batch``, whose ``device_put`` is
    asynchronous, so the device transfer ALSO starts ahead of consumption;
    this is how the loader composes with the existing device prefetch),
    and hands results over a bounded queue. Contract:

      - iteration order and content are EXACTLY `transform(x) for x in
        src` — the thread moves work, never semantics;
      - a producer-side exception is re-raised on the CONSUMING thread at
        the position it occurred (KFTPU-EXCEPT clean: never swallowed);
      - `close()` (or exhaustion) joins the worker — an early-exiting
        consumer leaks no thread; idempotent, safe from `finally`;
      - per-batch timing lands on `last_wait_s` (consumer blocked time —
        what the step critical path actually paid) and
        `last_assemble_s` (producer host work — overlapped), the numbers
        the trainer stamps on its `train.data_load` spans so the step
        breakdown splits queue-wait from host-assemble;
      - locks are lockcheck-named (analysis/lockcheck.py), so the
        KFTPU_LOCKCHECK=1 drills see the loader's lock in the global
        acquisition-order graph.
    """

    def __init__(
        self,
        src: Iterator,
        transform: Callable | None = None,
        size: int = 2,
        mesh=None,
        name: str = "train.loader",
    ):
        from kubeflow_tpu.analysis.lockcheck import make_lock

        self._src = iter(src)
        self._transform = transform
        self._mesh = mesh
        self._size = max(1, size)
        self._mu = make_lock(f"data.AsyncLoader._mu[{name}]")
        self._not_empty = threading.Condition(self._mu)
        self._not_full = threading.Condition(self._mu)
        self._buf: list = []          # bounded by _size
        self._done = False            # producer exhausted src
        self._stopped = False         # consumer closed
        self._exc: BaseException | None = None
        self.last_wait_s = 0.0
        self.last_assemble_s = 0.0
        global _LIVE_LOADERS
        with _LOADER_MU:
            _LOADER_METRICS["threads_started_total"] += 1
            _LIVE_LOADERS += 1
        self._counted_live = True
        self._thread = threading.Thread(
            target=self._run, name=f"kftpu-{name}", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ producer

    def _run(self) -> None:
        try:
            if self._mesh is not None:
                import jax

                with jax.set_mesh(self._mesh):
                    self._produce()
            else:
                self._produce()
        except _LoaderStop:
            # consumer closed early — normal shutdown; still mark done so
            # a straggling next() can never block on a dead producer
            with self._mu:
                self._done = True
                self._not_empty.notify_all()
        except BaseException as e:  # noqa: BLE001 — carried to the consumer
            with self._mu:
                self._exc = e
                self._done = True
                self._not_empty.notify_all()
            with _LOADER_MU:
                _LOADER_METRICS["errors_total"] += 1
        else:
            with self._mu:
                self._done = True
                self._not_empty.notify_all()
        finally:
            # the live gauge tracks RUNNING loader threads: every terminal
            # path drops it here (natural exhaustion included — a drained
            # loader never shows as a phantom leak), while a producer
            # wedged inside transform never reaches this and keeps its
            # count — exactly the leak kftpu_train_loader_live exposes
            global _LIVE_LOADERS
            with _LOADER_MU:
                if self._counted_live:
                    self._counted_live = False
                    _LIVE_LOADERS -= 1

    def _produce(self) -> None:
        for item in self._src:
            t0 = time.perf_counter()
            out = self._transform(item) if self._transform else item
            dt = time.perf_counter() - t0
            with _LOADER_MU:
                _LOADER_METRICS["assemble_seconds_total"] += dt
            with self._mu:
                while len(self._buf) >= self._size and not self._stopped:
                    self._not_full.wait(timeout=0.1)
                if self._stopped:
                    raise _LoaderStop
                self._buf.append((out, dt))
                self._not_empty.notify()

    # ------------------------------------------------------------ consumer

    def __iter__(self) -> "AsyncLoader":
        return self

    def __next__(self):
        t0 = time.perf_counter()
        with self._mu:
            while not self._buf and not self._done:
                self._not_empty.wait(timeout=0.1)
            if self._buf:
                out, assemble = self._buf.pop(0)
                self._not_full.notify()
            else:
                exc = self._exc
                self._exc = None
                if exc is not None:
                    raise exc  # the producer's failure, on OUR thread
                raise StopIteration
        wait = time.perf_counter() - t0
        self.last_wait_s = wait
        self.last_assemble_s = assemble
        with _LOADER_MU:
            _LOADER_METRICS["batches_total"] += 1
            _LOADER_METRICS["queue_wait_seconds_total"] += wait
        return out

    def pop_stats(self) -> dict[str, float]:
        """Timing of the most recent batch — stamped onto the consumer's
        train.data_load span (wait is ON the critical path; assemble is
        the overlapped producer work, reported for the overlap ratio)."""
        return {"wait_s": self.last_wait_s,
                "assemble_s": self.last_assemble_s}

    def close(self) -> None:
        """Stop the producer and JOIN its thread (no daemon leak); safe to
        call repeatedly and after exhaustion. The bounded buffer is
        dropped — a closing consumer wants out, not the backlog (a
        straggling next() gets StopIteration, never a stale pre-close
        batch). A producer wedged inside `transform` (join times out)
        keeps its live-loader count: kftpu_train_loader_live exists to
        expose exactly that leak (the producer's own exit clears it)."""
        with self._mu:
            self._stopped = True
            self._buf.clear()
            self._not_full.notify_all()
            self._not_empty.notify_all()
        self._thread.join(timeout=30.0)

    def __enter__(self) -> "AsyncLoader":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


# ------------------------------------------------------------- sharded files

def save_dataset_shards(ds: Dataset, out_dir: str, num_shards: int = 8) -> str:
    """Write a Dataset as numbered .npz shards + manifest — the on-disk
    contract multi-host gangs load per-process (reference analogue:
    tf.data file sharding / torch DistributedSampler; here the unit is a
    shard FILE so host reads never overlap)."""
    import json as _json
    from pathlib import Path as _Path

    d = _Path(out_dir)
    d.mkdir(parents=True, exist_ok=True)
    n = len(ds.x_train)
    num_shards = max(1, min(num_shards, n))
    bounds = np.linspace(0, n, num_shards + 1, dtype=int)
    for i in range(num_shards):
        lo, hi = bounds[i], bounds[i + 1]
        np.savez(d / f"train-{i:05d}.npz",
                 x=ds.x_train[lo:hi], y=ds.y_train[lo:hi])
    np.savez(d / "test.npz", x=ds.x_test, y=ds.y_test)
    (d / "manifest.json").write_text(_json.dumps({
        "num_shards": num_shards,
        "num_classes": int(ds.num_classes),
        "n_train": int(n),
    }))
    return str(d)


def load_dataset_shards(
    data_dir: str,
    process_id: int | None = None,
    num_processes: int | None = None,
) -> Dataset:
    """Load a sharded dataset, taking only THIS process's shard files
    (round-robin by index) in a multi-process gang — each host reads a
    disjoint subset, the per-host data-parallel contract. Defaults to the
    ambient jax.distributed topology; (0, 1) outside a gang.

    The test split is replicated to every process (eval is cheap and the
    Trainer's eval runs on the global batch)."""
    import json as _json
    from pathlib import Path as _Path

    if (process_id is None) != (num_processes is None):
        raise ValueError(
            "pass BOTH process_id and num_processes, or neither (ambient "
            "jax.distributed topology)"
        )
    if process_id is None:
        import jax

        process_id = jax.process_index()
        num_processes = jax.process_count()
    d = _Path(data_dir)
    meta = _json.loads((d / "manifest.json").read_text())
    num_shards = int(meta["num_shards"])
    if num_shards < num_processes:
        raise ValueError(
            f"{num_shards} shard(s) cannot feed {num_processes} processes; "
            f"re-shard with num_shards >= the gang size"
        )
    # every process must end with the SAME row count or gang step counts
    # drift and a collective deadlocks; shard sizes are deterministic from
    # the manifest, so each process computes the global minimum locally
    bounds = np.linspace(0, int(meta["n_train"]), num_shards + 1, dtype=int)
    sizes = bounds[1:] - bounds[:-1]
    limit = min(
        int(sizes[p::num_processes].sum()) for p in range(num_processes)
    )
    xs, ys = [], []
    for i in range(process_id, num_shards, num_processes):
        with np.load(d / f"train-{i:05d}.npz") as z:
            xs.append(z["x"])
            ys.append(z["y"])
    with np.load(d / "test.npz") as test:
        x_test, y_test = test["x"], test["y"]
    return Dataset(
        np.concatenate(xs)[:limit], np.concatenate(ys)[:limit],
        x_test, y_test, int(meta["num_classes"]),
    )
