"""DeviceFeed: Arrow blocks → device-sharded ``jax.Array`` batches.

This is the TPU-specific tail of the data plane, replacing the reference's
``dataset.to_torch`` + DataLoader feed (torch/estimator.py:226-241) and its
background-prefetch trick (``PrefetchedDataLoader``, torch_ml_dataset.py:69-108).
Design for the hardware: batches are assembled host-side as contiguous numpy
(decode is zero-copy out of shared memory wherever Arrow allows), then placed with
``jax.device_put`` under a ``NamedSharding`` over the mesh's data axis, so the
train step's inputs are already distributed and XLA inserts no gather. Shapes are
static (``drop_remainder``) — a changing batch dimension would retrace/recompile
under jit.

The streaming pipeline is ASYNC and DOUBLE-BUFFERED (:class:`DevicePrefetcher`):
a host stage keeps ``prefetch`` decoded batches ahead, and a device stage keeps
``prefetch_to_device`` already-``device_put`` batches ahead, so the H2D transfer
for batch ``k+1`` overlaps the jitted compute of batch ``k``. The reference
prefetches only *host* batches; pipelining the device side is what removes
``device_put`` from the step critical path.
Per-phase walls (``decode``/``h2d``) accumulate in
:class:`PipelineTimings` and surface in the estimators' epoch reports.

Multi-host: each process feeds its own shard and the global array is built with
``jax.make_array_from_process_local_data`` — the per-host ``device_put`` endpoint
of SURVEY.md §2.5's "TPU-native equivalent".
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import pyarrow as pa

from raydp_tpu import knobs, metrics, profiler
from raydp_tpu.log import get_logger

logger = get_logger("data.feed")


@dataclass
class ShardSpec:
    """What one data-parallel rank reads: ``(block_index, offset, length)``."""

    parts: List[Tuple[int, int, int]] = field(default_factory=list)

    def num_rows(self) -> int:
        return sum(n for _, _, n in self.parts)


ColumnSpec = Union[str, Sequence[str]]

#: batch-dict key carrying the per-row validity mask under pad-and-mask mode
#: (1.0 = real row, 0.0 = padding). Present on EVERY batch a padding feed
#: yields — a constant pytree structure keeps the jitted step at one
#: compilation — and threaded by the estimators into loss/metric
#: accumulators so padded rows contribute nothing.
MASK_KEY = "__mask__"


def pad_batch(batch: Dict[str, np.ndarray], batch_size: int
              ) -> Dict[str, np.ndarray]:
    """Zero-pad a ragged host batch up to ``batch_size`` rows and attach the
    validity mask. Shapes come out static (one XLA program) and divisible by
    any data-axis extent that divides ``batch_size`` — the alternative the
    pre-pad feed took was silently DROPPING the tail rows under a >1 data
    axis."""
    rows = int(next(iter(batch.values())).shape[0])
    pad = batch_size - rows
    if pad < 0:
        raise ValueError(f"batch of {rows} rows exceeds batch_size "
                         f"{batch_size}")
    mask = np.zeros(batch_size, np.float32)
    mask[:rows] = 1.0
    if pad:
        batch = {n: np.concatenate(
            [a, np.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)
            for n, a in batch.items()}
        metrics.inc("train_padded_rows_total", pad)
    else:
        batch = dict(batch)
    batch[MASK_KEY] = mask
    return batch


def epoch_seed(base: int, epoch: int) -> int:
    """Deterministic per-epoch shuffle seed — THE derivation every feed path
    shares (DeviceFeed.set_epoch and both external-loop bridges), so the
    bridges cannot drift from the native data-plane semantics."""
    return (base + epoch * 1000003) % (2**31 - 1)


def _normalize_columns(columns: Dict[str, Tuple[ColumnSpec, np.dtype]]
                       ) -> Dict[str, Tuple[Tuple[str, ...], np.dtype]]:
    return {
        name: ((cols,) if isinstance(cols, str) else tuple(cols), np.dtype(dt))
        for name, (cols, dt) in columns.items()
    }


def _as_numpy(table: pa.Table, columns: Sequence[str], dtype) -> np.ndarray:
    """Stack columns into [rows, len(columns)] (or [rows] for one column).

    Multi-column decode goes through the native staging kernel when eligible
    (csrc/feed/stage.cpp: cast+interleave fused into one pass per column,
    straight from the Arrow data buffers — SURVEY.md §7 step 2's "Arrow ↔
    host buffer staging"); null-bearing/non-primitive columns and a library
    that cannot be built take the numpy path below, output-identical
    (tests/test_native_stage.py). Which one ran is counted, so a run can say
    what it measured.

    One column of a fixed-size-list type (one row = one packed sequence of
    tokens) comes out ``[rows, list_size]``: the child values cast in one
    flat pass, natively where eligible, and counted the same way."""
    if len(columns) == 1:
        col = table.column(columns[0])
        if not pa.types.is_fixed_size_list(col.type):
            return col.to_numpy(zero_copy_only=False).astype(dtype,
                                                             copy=False)
        from raydp_tpu.native.stage import stage_list_column
        staged = stage_list_column(col, dtype)
        metrics.inc("feed_staged_tables_total",
                    label="numpy" if staged is None else "native")
        if staged is not None:
            return staged
        if col.null_count:
            raise ValueError(f"list column {columns[0]!r} holds nulls")
        flat = [c.flatten().to_numpy(zero_copy_only=False)
                for c in col.chunks]
        return (np.concatenate(flat) if flat else np.empty(0)).reshape(
            len(col), col.type.list_size).astype(dtype, copy=False)
    from raydp_tpu.native.stage import stage_table
    staged = stage_table(table, columns, dtype)
    metrics.inc("feed_staged_tables_total",
                label="numpy" if staged is None else "native")
    if staged is not None:
        return staged
    return np.stack([
        table.column(c).to_numpy(zero_copy_only=False).astype(dtype,
                                                              copy=False)
        for c in columns], axis=1)


class HostBatchIterator:
    """Yields host-side numpy batch dicts from a dataset (or one shard of it).

    Decoded blocks are cached across epochs (``cache_decoded``, on by
    default, bounded by ``RDT_FEED_CACHE_MB``): Arrow→numpy decode + dtype
    cast is the dominant host cost of an epoch once the train step is fast,
    and multi-epoch training re-reads the same immutable blocks. Per-epoch
    shuffling permutes indices over the cached arrays instead of re-decoding.

    How a batch is cut: an epoch is its parts in (shuffled) order, each part
    permuted whole — ``rng.shuffle(parts)``, then ONE ``rng.permutation`` a
    part — and cut every ``batch_size`` rows. Only the permutation is drawn a
    part at a time; the rows are copied a batch at a time, ``a[idx[s:s +
    batch_size]]`` a column: one gather of ``batch_size`` rows, so the first
    batch of an epoch costs a batch and not its block, and an epoch copies
    each row once. A batch that spans two parts is the tail gather of one
    joined to the head gather of the next; without shuffling a batch inside a
    part is a contiguous view (read-only where the cache holds the block). A
    block the cache does not keep lives until its last batch is cut.
    ``feed_batches_cut_total{gathered|joined|sliced}`` counts which.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        columns: Dict[str, Tuple[ColumnSpec, np.dtype]],
        shard: Optional[ShardSpec] = None,
        shuffle: bool = True,
        seed: int = 0,
        drop_remainder: bool = True,
        cache_decoded: bool = True,
        cache_cap_bytes: Optional[int] = None,
        pad_remainder: bool = False,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.columns = _normalize_columns(columns)
        self.shard = shard
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder and not pad_remainder
        #: pad-and-mask mode: the ragged tail pads to a full batch and EVERY
        #: batch carries :data:`MASK_KEY` (constant pytree structure — one
        #: jit compilation); wins over drop_remainder
        self.pad_remainder = pad_remainder
        self.cache_decoded = cache_decoded
        # per-iterator budget (train and eval feeds each get their own); env
        # read at construction so callers can tune it after import
        self.cache_cap_bytes = cache_cap_bytes if cache_cap_bytes is not None \
            else int(float(knobs.get("RDT_FEED_CACHE_MB")) * (1 << 20))
        self._decoded: Dict[int, Dict[str, np.ndarray]] = {}
        self._cache_bytes = 0
        self._sizes: Optional[List[int]] = None

    def _block_sizes(self) -> List[int]:
        if self._sizes is None:
            self._sizes = list(self.dataset.block_sizes())
        return self._sizes

    def _parts(self) -> List[Tuple[int, int, int]]:
        if self.shard is not None:
            return list(self.shard.parts)
        return [(i, 0, n) for i, n in enumerate(self._block_sizes())]

    def _block_rows(self, block_idx: int) -> int:
        return self._block_sizes()[block_idx]

    def _decode_block(self, block_idx: int) -> Dict[str, np.ndarray]:
        """Decode (and maybe cache) ALL rows of a block."""
        cached = self._decoded.get(block_idx)
        if cached is not None:
            return cached
        table = self.dataset.get_block(block_idx, zero_copy=True)
        arrays = {name: _as_numpy(table, cols, dt)
                  for name, (cols, dt) in self.columns.items()}
        if self.cache_decoded:
            size = sum(a.nbytes for a in arrays.values())
            if self._cache_bytes + size <= self.cache_cap_bytes:
                # own the bytes: a zero-copy view into the store must not be
                # cached past this iteration (the block could be freed)
                arrays = {n: (a if a.flags["OWNDATA"] else a.copy())
                          for n, a in arrays.items()}
                for a in arrays.values():
                    # batches served from the cache are views; freezing the
                    # cache turns an in-place consumer mutation (which would
                    # silently poison later epochs) into a loud error
                    a.setflags(write=False)
                self._decoded[block_idx] = arrays
                self._cache_bytes += size
        return arrays

    def _decode_slice(self, block_idx: int, off: int,
                      length: int) -> Dict[str, np.ndarray]:
        """Decode just ``[off, off+length)`` — used for partial shard parts
        so a rank neither decodes nor budgets rows it never reads."""
        table = self.dataset.get_block(block_idx,
                                       zero_copy=True).slice(off, length)
        return {name: _as_numpy(table, cols, dt)
                for name, (cols, dt) in self.columns.items()}

    def __iter__(self):
        rng = np.random.RandomState(self.seed)
        parts = self._parts()
        if self.shuffle:
            rng.shuffle(parts)
        # rows decoded and not yet cut, in the epoch's order: (arrays, rows)
        # runs, where ``rows`` names what is left of a part in its block's
        # arrays — the rest of the part's permutation, or a range — and is
        # never empty. A run keeps its block's arrays until its last row is
        # cut, cached or not
        runs: Deque[Tuple[Dict[str, np.ndarray],
                          Union[np.ndarray, range]]] = deque()
        buffered = 0
        for block_idx, off, length in parts:
            if not length:
                continue
            full_block = off == 0 and length == self._block_rows(block_idx)
            if full_block or block_idx in self._decoded:
                arrays, start = self._decode_block(block_idx), off
            else:
                arrays, start = self._decode_slice(block_idx, off, length), 0
            # the permutation is drawn a part, whole; the rows it names are
            # copied a batch at a time, by the batch's slice of it
            runs.append((arrays, start + rng.permutation(length)
                         if self.shuffle and length > 1
                         else range(start, start + length)))
            buffered += length
            while buffered >= self.batch_size:
                yield self._cut_batch(runs, self.batch_size)
                buffered -= self.batch_size
        if buffered > 0 and not self.drop_remainder:
            yield self._cut_batch(runs, buffered)

    def _cut_batch(self, runs, want: int) -> Dict[str, np.ndarray]:
        """The next ``want`` rows off the front of ``runs``: ONE gather of a
        permutation's slice (or one contiguous view) where a run holds them
        all, else the tail of one part joined to the head of the next."""
        pieces = []
        while want:
            arrays, rows = runs[0]
            head, rest = rows[:want], rows[want:]
            if len(rest):
                runs[0] = (arrays, rest)
            else:
                runs.popleft()
            want -= len(head)
            sliced = isinstance(head, range)
            if sliced:
                head = slice(head.start, head.stop)
            pieces.append({n: a[head] for n, a in arrays.items()})
        if len(pieces) > 1:
            cut = "joined"
            batch = {n: np.concatenate([p[n] for p in pieces], axis=0)
                     for n in self.columns}
        else:
            cut = "sliced" if sliced else "gathered"
            batch = pieces[0]
        metrics.inc("feed_batches_cut_total", label=cut)
        return pad_batch(batch, self.batch_size) \
            if self.pad_remainder else batch


def process_local_batch_rows(sharding, global_batch: int) -> Tuple[int, int]:
    """The contiguous ``[start, stop)`` slice of a ``(global_batch,)`` array
    that THIS process's devices address under ``sharding``.

    This is what a gang rank must feed ``make_array_from_process_local_data``:
    with the batch sharded over a >1 data axis spanning processes it is a
    proper slice; with the batch replicated across processes (size-1 data axis
    — pure fsdp/expert meshes) it is the full ``(0, global_batch)`` range on
    every process.
    """
    idx_map = sharding.addressable_devices_indices_map((global_batch,))
    intervals = set()
    for idx in idx_map.values():
        sl = idx[0] if idx else slice(None)
        intervals.add((sl.start or 0,
                       global_batch if sl.stop is None else sl.stop))
    lo = min(s for s, _ in intervals)
    hi = max(e for _, e in intervals)
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            raise ValueError(
                f"process-local batch rows are not contiguous under {sharding}"
                f": gap at [{cur}, {s})")
        cur = max(cur, e)
    return int(lo), int(hi)


class GangShardIterator:
    """Per-rank host batches that compose into globally-consistent batches.

    Global batch ``k`` covers dataset rows ``[k*B, (k+1)*B)`` in block order —
    exactly the batches a single-process :class:`HostBatchIterator` with
    ``shuffle=False`` cuts — and rank ``r`` of ``w`` yields its addressable
    slice of each: ``row_range`` (derived from the batch sharding via
    :func:`process_local_batch_rows`) when given, else the equal split
    ``[r*B/w, (r+1)*B/w)``. All ranks permute the *batch order* with the same
    seed (no within-block shuffling), so every rank walks the same global
    batch sequence and ``jax.make_array_from_process_local_data`` assembles
    the intended global array. This is the multi-host analogue of the
    reference's per-worker dataset shard (torch/estimator.py:226-241 via
    ``divide_blocks``), strengthened to give bit-identical global batches for
    any world size.

    The rows past the last full global batch are dropped, or with
    ``pad_remainder`` travel as one more global batch that is zero-padded to
    full size: every batch then carries this rank's slice of the validity
    mask (:data:`MASK_KEY`, as :class:`HostBatchIterator` in that mode), and
    a rank whose slice lies wholly past the last row yields padding alone.
    """

    def __init__(
        self,
        dataset,
        global_batch: int,
        world_size: int,
        rank: int,
        columns: Dict[str, Tuple[ColumnSpec, np.dtype]],
        shuffle: bool = False,
        seed: int = 0,
        row_range: Optional[Tuple[int, int]] = None,
        pad_remainder: bool = False,
    ):
        if not (0 <= rank < world_size):
            raise ValueError(f"rank {rank} out of range for world {world_size}")
        if row_range is None:
            if global_batch % world_size != 0:
                raise ValueError(
                    f"global batch {global_batch} not divisible by world size "
                    f"{world_size}")
            per = global_batch // world_size
            row_range = (rank * per, (rank + 1) * per)
        lo, hi = row_range
        if not (0 <= lo < hi <= global_batch):
            raise ValueError(f"row_range {row_range} out of range for "
                             f"global batch {global_batch}")
        self.dataset = dataset
        self.global_batch = global_batch
        self.world_size = world_size
        self.rank = rank
        self.columns = _normalize_columns(columns)
        self.shuffle = shuffle
        self.seed = seed
        self.row_range = (int(lo), int(hi))
        self.per_rank = int(hi) - int(lo)
        self.pad_remainder = pad_remainder
        self._starts = np.cumsum([0] + list(dataset.block_sizes()))
        self.total = int(self._starts[-1])
        # decoded-block cache across epochs (HostBatchIterator's trick):
        # without it every rank re-runs Arrow→numpy decode for every batch
        # of every epoch — the dominant per-epoch host cost of a gang rank
        self._decoded: Dict[int, Dict[str, np.ndarray]] = {}
        self._cache_bytes = 0
        self._cache_cap = int(float(knobs.get("RDT_FEED_CACHE_MB"))
                              * (1 << 20))

    def __len__(self) -> int:
        if self.pad_remainder:
            return -(-self.total // self.global_batch)
        return self.total // self.global_batch

    def _runs(self, start: int, stop: int) -> List[Tuple[int, int, int]]:
        """Global row range → list of (block_index, offset, length) runs."""
        runs: List[Tuple[int, int, int]] = []
        b = int(np.searchsorted(self._starts, start, side="right")) - 1
        row = start
        while row < stop:
            blk_end = int(self._starts[b + 1])
            take = min(stop, blk_end) - row
            runs.append((b, row - int(self._starts[b]), take))
            row += take
            b += 1
        return runs

    def _decoded_nbytes(self, rows: int) -> int:
        """Exact decoded size of ``rows`` rows under this iterator's fixed-
        width column specs — lets cache eligibility be decided WITHOUT
        decoding the block first."""
        return rows * sum(len(cols) * dt.itemsize
                          for cols, dt in self.columns.values())

    def _decode_run(self, b: int, off: int,
                    length: int) -> Dict[str, np.ndarray]:
        """Rows ``[off, off+length)`` of block ``b``: served from the decoded
        cache when the block fits the ``RDT_FEED_CACHE_MB`` budget; otherwise
        only the requested slice is decoded (``table.slice`` is zero-copy),
        so an over-cap gang feed pays O(batch) — not O(block) — Arrow→numpy
        work per batch (mirrors ``HostBatchIterator._decode_slice``)."""
        cached = self._decoded.get(b)
        if cached is None and (self._cache_bytes
                               + self._decoded_nbytes(self._block_rows(b))
                               <= self._cache_cap):
            table = self.dataset.get_block(b, zero_copy=True)
            arrays = {name: _as_numpy(table, cols, dt)
                      for name, (cols, dt) in self.columns.items()}
            # own the bytes (a zero-copy view into the store must not be
            # cached past this iteration) and freeze them so an in-place
            # consumer mutation fails loudly instead of poisoning epochs
            arrays = {n: (a if a.flags["OWNDATA"] else a.copy())
                      for n, a in arrays.items()}
            for a in arrays.values():
                a.setflags(write=False)
            cached = self._decoded[b] = arrays
            self._cache_bytes += sum(a.nbytes for a in arrays.values())
        if cached is not None:
            return {n: a[off:off + length] for n, a in cached.items()}
        table = self.dataset.get_block(b, zero_copy=True).slice(off, length)
        return {name: _as_numpy(table, cols, dt)
                for name, (cols, dt) in self.columns.items()}

    def _block_rows(self, b: int) -> int:
        return int(self._starts[b + 1] - self._starts[b])

    def __iter__(self):
        order = np.arange(len(self))
        if self.shuffle:
            np.random.RandomState(self.seed).shuffle(order)
        for k in order:
            start = int(k) * self.global_batch + self.row_range[0]
            # only the padded tail batch ends before its full extent; a slice
            # wholly past the last row decodes an empty run (shapes, dtypes)
            stop = min(start + self.per_rank, self.total)
            runs = self._runs(start, stop) or [(len(self._starts) - 2, 0, 0)]
            parts = [self._decode_run(b, off, length)
                     for b, off, length in runs]
            batch = parts[0] if len(parts) == 1 else {
                n: np.concatenate([p[n] for p in parts], axis=0)
                for n in self.columns}
            yield pad_batch(batch, self.per_rank) \
                if self.pad_remainder else batch


class DeviceEpochCache:
    """The whole dataset resident in device memory: epoch = ONE dispatch.

    TPU-first feed design for datasets that fit an HBM budget (the reference's
    tabular workloads are tens of MB against 16 GB of HBM): decode every block
    once, concatenate to contiguous host arrays, and ``device_put`` them under
    the mesh's batch sharding. The train loop then runs a whole epoch as a
    single jitted ``lax.scan`` whose body *slices batches on device* — with
    per-epoch shuffling as an on-device ``jax.random.permutation`` — so the
    steady-state host cost of an epoch is one dispatch and one scalar fetch.

    This replaces, for resident datasets, three O(dataset)-per-epoch host
    costs the streaming path pays: Arrow→numpy feed assembly, the per-epoch
    executor-side re-shuffle, and one dispatch per step. The streaming
    :class:`DeviceFeed` remains the path for datasets above the budget and
    for multi-process gangs (where each process owns only its shard).
    """

    def __init__(self, dataset, columns: Dict[str, Tuple[ColumnSpec, np.dtype]],
                 mesh=None):
        import jax

        cols = _normalize_columns(columns)
        host: Dict[str, List[np.ndarray]] = {n: [] for n in cols}
        for i in range(dataset.num_blocks()):
            table = dataset.get_block(i, zero_copy=True)
            for name, (cnames, dt) in cols.items():
                host[name].append(_as_numpy(table, cnames, dt))
        joined = {n: (np.concatenate(v, axis=0) if len(v) > 1 else v[0])
                  for n, v in host.items()}
        self.num_rows = int(next(iter(joined.values())).shape[0])
        self.nbytes = sum(a.nbytes for a in joined.values())
        self.mesh = mesh
        if mesh is not None:
            # REPLICATED across the mesh: the row count need not divide the
            # data axes (a row-sharded layout would require it), and the
            # eligibility budget already bounds the per-device bytes. The
            # train loop's per-batch sharding constraint re-distributes each
            # sliced batch over the data axes
            from jax.sharding import NamedSharding, PartitionSpec
            self.sharding = NamedSharding(mesh, PartitionSpec())
            self.arrays = {n: jax.device_put(a, self.sharding)
                           for n, a in joined.items()}
        else:
            self.sharding = None
            self.arrays = {n: jax.device_put(a) for n, a in joined.items()}
        # one host row for shape/dtype-driven model init; the big host copies
        # are dropped once resident on device
        self.init_row = {n: a[:1].copy() for n, a in joined.items()}

    def make_epoch_fn(self, step, batch_size: int, shuffle: bool,
                      batch_sharding=None, seq_sharding=None):
        """Build THE resident epoch program both estimators jit — one source
        for the permutation/slice/constraint/scan logic.

        ``step(carry, batch) -> carry`` is the caller's train step in scan
        form. Returns ``(epoch_fn, steps_per_epoch)`` with
        ``epoch_fn(carry, data, key) -> carry``: one whole epoch —
        per-epoch on-device permutation when ``shuffle`` (a true uniform row
        shuffle), batches sliced/gathered on device, each constrained onto
        the mesh's batch sharding — ndim >= 2 leaves onto ``seq_sharding``
        when one is given, so declared sequence dims spread over the mesh's
        ``seq`` axis. Callers jit it with the carry donated and
        ``data``/``key`` left alone (the resident arrays are reused every
        epoch).
        """
        import jax
        import jax.numpy as jnp
        from jax import lax

        n_rows, B = self.num_rows, batch_size
        steps_per_epoch = n_rows // B

        def epoch_fn(carry, data, key):
            if not steps_per_epoch:
                # fewer rows than one batch (an evaluation set; ``eligible``
                # keeps such a training set off this path): the body's slice
                # of B rows would not trace, and the caller's tail call
                # serves every row
                return carry
            perm = jax.random.permutation(key, n_rows) if shuffle else None

            def body(carry, s):
                if perm is not None:
                    idx = lax.dynamic_slice(perm, (s * B,), (B,))
                    batch = {n: jnp.take(a, idx, axis=0)
                             for n, a in data.items()}
                else:
                    batch = {n: lax.dynamic_slice_in_dim(a, s * B, B, 0)
                             for n, a in data.items()}
                if batch_sharding is not None:
                    if seq_sharding is not None:
                        batch = {
                            n: lax.with_sharding_constraint(
                                a, seq_sharding if a.ndim >= 2
                                else batch_sharding)
                            for n, a in batch.items()}
                    else:
                        batch = lax.with_sharding_constraint(batch,
                                                             batch_sharding)
                return step(carry, batch), ()

            carry, _ = lax.scan(body, carry, jnp.arange(steps_per_epoch))
            return carry

        return epoch_fn, steps_per_epoch

    @staticmethod
    def cap_bytes() -> int:
        return int(float(knobs.get("RDT_DEVICE_CACHE_MB")) * (1 << 20))

    @staticmethod
    def estimate_bytes(dataset,
                       columns: Dict[str, Tuple[ColumnSpec, np.dtype]]) -> int:
        rows = sum(dataset.block_sizes())
        schema = dataset.schema

        def width(name):    # a fixed-size-list column decodes to its size
            t = schema.field(name).type if name in schema.names else None
            return t.list_size if t is not None \
                and pa.types.is_fixed_size_list(t) else 1

        per_row = sum(sum(map(width, cnames)) * np.dtype(dt).itemsize
                      for cnames, dt in _normalize_columns(columns).values())
        return rows * per_row

    @classmethod
    def eligible(cls, dataset,
                 columns: Dict[str, Tuple[ColumnSpec, np.dtype]],
                 batch_size: int, drop_last: bool) -> bool:
        """THE residency gate — the single decision every call site (the feed
        plan of ``train/loop.py``, the fit_on_frame shuffle-skip) must share, or
        a drifted copy could e.g. skip the dataset-level shuffle while fit()
        streams.
        Requires: opted in, single process (a gang rank only holds its shard —
        global batches there need the per-rank feed), static full batches
        (``drop_last`` with at least one batch of rows), and decoded arrays
        within the HBM budget."""
        import jax

        if not knobs.get("RDT_DEVICE_CACHE"):
            return False
        if not drop_last or jax.process_count() > 1:
            return False
        cap = cls.cap_bytes()  # outside the try: a malformed
        # RDT_DEVICE_CACHE_MB should raise loudly, not silently stream
        try:
            if sum(dataset.block_sizes()) < batch_size:
                return False
            return cls.estimate_bytes(dataset, columns) <= cap
        except Exception:  # noqa: BLE001 - unknown size: stream
            return False


class PipelineTimings:
    """Thread-safe per-phase wall accumulator for the feed pipeline.

    Phases (surfaced per epoch as ``decode_time_s``/``h2d_time_s`` by both
    estimators):

    - ``decode`` — host batch production: Arrow→numpy decode (native staging
      kernel included) plus the host iterator's own batch assembly.
    - ``h2d``    — device placement: ``jax.device_put`` /
      ``make_array_from_process_local_data`` under the feed's sharding.

    The timers run on the pipeline's background threads, so phase walls
    OVERLAP the consumer's dispatch wall by design — pipeline wall-clock
    under the sum of phase walls is the overlap win, measured directly by
    ``benchmarks/host_decode_bench.py --overlap``.

    What the walls are NOT: ``h2d`` times the ``device_put`` *call*, which
    returns before the copy ends; and the estimators' ``dispatch_time_s``
    beside these includes the time the jitted call waits for the device's
    queue, so on a busy chip it measures the device, not the host. The real
    thing is on the device trace's clock: the ``feed:h2d`` row of a trace
    (``profiler.step``) against the device's transfers, and the device idle
    time under ``train:dispatch`` (chipbench's ``idle_dispatch_share``).
    """

    KEYS = ("decode", "h2d")

    def __init__(self):
        self._lock = threading.Lock()
        self._acc = {k: 0.0 for k in self.KEYS}

    def add(self, key: str, dt: float) -> None:
        with self._lock:
            self._acc[key] += dt
        # the registry twin: the same observation flows into the typed
        # metrics plane so metrics_report() sees feed phases without the
        # estimators re-publishing their epoch dicts
        metrics.observe("feed_phase_seconds", dt, label=key)

    def take(self) -> Dict[str, float]:
        """Snapshot AND reset — each epoch reports its own split."""
        with self._lock:
            out = dict(self._acc)
            for k in self._acc:
                self._acc[k] = 0.0
        return out


class DevicePrefetcher:
    """Bounded async stage of the device-feed pipeline (double buffering).

    Pulls items from ``src`` on a background thread, applies ``fn`` (the
    device stage passes ``jax.device_put`` under the feed's sharding), and
    keeps up to ``depth`` results queued ahead of the consumer, so staging +
    H2D for batch ``k+1`` overlap the jitted compute of batch ``k``. The
    bounded queue IS the backpressure: the producer can run at most
    ``depth + 1`` items ahead. Producer exceptions re-raise in the consumer;
    closing (or abandoning) the iterator stops the thread — an estimator
    error cannot leak one producer per epoch. Single-use: one ``iter()`` per
    instance.

    ``pull_key`` names the :class:`PipelineTimings` phase the ``next(src)``
    pull accumulates into (the host stage times its pulls as ``decode``; the
    device stage's placement is timed by the feed so the sync path measures
    identically). ``pull_span`` names the
    STEP span of that pull (the host stage's is ``feed:decode``; a stage
    whose pull only waits on the stage before it has none). ``count_pulls``
    marks the stage a train loop pulls from: its consumer side counts
    ``feed_pulls_total{ready|empty}``. ``stop_span`` names the STEP span of
    the ``close()`` that ends the iteration, on the consumer's thread (the
    feed's is ``feed:stop``: the close of the stage the loop pulls from stops
    and joins the whole chain behind it).
    """

    _DONE = object()

    def __init__(self, src, fn=None, depth: int = 2, timings=None,
                 pull_key: Optional[str] = None,
                 name: str = "devicefeed-prefetch",
                 pull_span: Optional[str] = None,
                 count_pulls: bool = False,
                 stop_span: Optional[str] = None):
        self._src = src
        self._fn = fn
        self._timings = timings
        self._pull_key = pull_key
        self._pull_span = pull_span
        self._count_pulls = count_pulls
        self._stop_span = stop_span
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        # the prefetch thread must trace under the constructing context
        # (a serve replica's staging pipeline, an estimator's feed) — a
        # plain Thread would drop the contextvar at the handoff
        self._ctx = profiler.capture()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=name)
        self._started = False

    def _run(self):
        with profiler.activate(self._ctx):
            self._run_inner()

    def _run_inner(self):
        try:
            src = iter(self._src)
            pull_span = self._pull_span
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    if pull_span is None:
                        item = next(src)
                    else:
                        with profiler.step(pull_span):
                            item = next(src)
                except StopIteration:
                    break
                if self._timings is not None and self._pull_key:
                    self._timings.add(self._pull_key,
                                      time.perf_counter() - t0)
                if self._fn is not None:
                    item = self._fn(item)
                if not self._put(item):
                    break
            self._put(self._DONE)  # no-op if stopped
        except BaseException as e:  # noqa: BLE001 - re-raised by the consumer
            self._put(e)
        finally:
            if self._stop.is_set():
                # stopped early: close() may already have run (and given up
                # after its join timeout if THIS thread was mid-fn), so the
                # upstream close falls to us — otherwise the host stage
                # before this one would keep decoding into its full queue
                # forever
                self._close_src()

    def _put(self, item) -> bool:
        """Blocking put that stays responsive to :meth:`close` (the timeout
        only ticks while the queue is FULL, i.e. the pipeline is ahead)."""
        if self._stop.is_set():
            return False
        try:
            self._q.put_nowait(item)
            return True
        except queue.Full:
            pass
        with profiler.step("feed:put_wait"):
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
        return False

    def _close_src(self) -> None:
        """Best-effort upstream cleanup: a generator src (e.g. the host
        stage's output, feeding the device stage) closes its own stage in
        its finally. Both the consumer's close() and the producer's finally
        may race here — generator.close() raises on the loser, swallowed
        below."""
        src_close = getattr(self._src, "close", None)
        if src_close is not None:
            try:
                src_close()
            except Exception:  # noqa: BLE001 - already shutting down
                pass

    def __iter__(self):
        if self._started:
            raise RuntimeError("DevicePrefetcher is single-use")
        self._started = True
        self._thread.start()
        try:
            while True:
                # the queue's state at the moment of the pull: a batch was
                # ready, or the consumer now waits for the producer
                empty = self._count_pulls and self._q.empty()
                item = self._q.get()
                if item is self._DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                if self._count_pulls:
                    metrics.inc("feed_pulls_total",
                                label="empty" if empty else "ready")
                yield item
        finally:
            if self._stop_span is None:
                self.close()
            else:
                with profiler.step(self._stop_span):
                    self.close()

    def _drain(self) -> None:
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def close(self) -> None:
        """Stop the producer and release queued buffers (idempotent)."""
        self._stop.set()
        self._drain()  # unblocks a producer waiting on a full queue
        if self._started and self._thread.is_alive():
            self._thread.join(timeout=5.0)
        self._drain()  # a mid-put producer may have landed one more item
        if not self._thread.is_alive():
            # thread gone (or never started): upstream close is on us; a
            # still-running thread (join timeout: mid-fn on a slow
            # device_put) closes upstream itself in _run's finally
            self._close_src()


class DeviceFeed:
    """Async double-buffered iterator of device-sharded batches.

    Two background stages feed the consumer: host decode (``prefetch``
    decoded batches ahead — the reference ``PrefetchedDataLoader``'s trick)
    and device placement (``prefetch_to_device`` already-placed batches
    ahead, so H2D for batch ``k+1`` overlaps the compute of batch ``k``;
    ``0`` restores synchronous placement — bit-identical results either way,
    tests/test_feed_pipeline.py). ``timings`` carries the per-phase
    decode/h2d split the estimators report per epoch."""

    def __init__(
        self,
        dataset,
        batch_size: int,
        columns: Dict[str, Tuple[ColumnSpec, np.dtype]],
        mesh=None,
        data_axis: Optional[str] = None,
        shard: Optional[ShardSpec] = None,
        shuffle: bool = True,
        seed: int = 0,
        prefetch: int = 2,
        drop_remainder: bool = True,
        host_iter=None,
        prefetch_to_device: Optional[int] = None,
        pad_remainder: bool = False,
        seq: bool = False,
    ):
        import jax
        self._jax = jax
        self.mesh = mesh
        self.data_axis = data_axis
        self.host_iter = host_iter if host_iter is not None else HostBatchIterator(
            dataset, batch_size, columns, shard=shard, shuffle=shuffle,
            seed=seed, drop_remainder=drop_remainder,
            pad_remainder=pad_remainder)
        self.prefetch = max(1, prefetch)
        if prefetch_to_device is None:
            prefetch_to_device = int(knobs.get("RDT_PREFETCH_TO_DEVICE"))
        #: already-placed batches kept ahead of the consumer (0 = place
        #: synchronously on the consumer thread)
        self.prefetch_to_device = max(0, int(prefetch_to_device))
        self.timings = PipelineTimings()
        self._shardings = None
        #: seq-extended sharding for ndim >= 2 batch leaves (None when the
        #: mesh has no >1 ``seq`` extent or the caller left ``seq`` off):
        #: declared sequence dims stage onto the ``seq`` axis at placement,
        #: so long-context activations never land whole on one device
        self._seq_sharding = None
        if mesh is not None:
            if data_axis is None:
                # the batch's true sharding spans data AND fsdp axes; using
                # only "data" on a pure-fsdp mesh would be a (size-1-axis)
                # replicated sharding, and in gang mode each process would
                # then assemble a DIFFERENT "replicated" array from its own
                # rows — silently inconsistent global batches
                from raydp_tpu.parallel.mesh import batch_sharding, seq_extent
                self._sharding = batch_sharding(mesh)
                if seq and seq_extent(mesh) > 1:
                    self._seq_sharding = batch_sharding(mesh, seq=True)
            else:
                from jax.sharding import NamedSharding, PartitionSpec
                self._sharding = NamedSharding(mesh, PartitionSpec(data_axis))
        else:
            self._sharding = None

    def set_epoch(self, epoch: int) -> None:
        """Reseed per-epoch so shuffling differs across epochs deterministically."""
        if not hasattr(self, "_base_seed"):
            self._base_seed = self.host_iter.seed
        self.host_iter.seed = epoch_seed(self._base_seed, epoch + 1)

    def _place(self, batch: Dict[str, np.ndarray]):
        jax = self._jax
        sharding, seq_sharding = self._sharding, self._seq_sharding
        if sharding is None:
            return {n: jax.device_put(a) for n, a in batch.items()}

        def pick(a):
            # only leaves with a dim past the batch axis carry a sequence
            # dim (labels/masks are 1-D and keep the plain data sharding)
            return seq_sharding if (seq_sharding is not None
                                    and a.ndim >= 2) else sharding

        if jax.process_count() > 1:
            return {
                n: jax.make_array_from_process_local_data(pick(a), a)
                for n, a in batch.items()
            }
        return {n: jax.device_put(a, pick(a)) for n, a in batch.items()}

    def _host_batches(self):
        """Host batches decoded ``prefetch`` ahead on a background thread;
        the pull wall (Arrow→numpy decode, native staging kernel included)
        accumulates as the ``decode`` phase. With synchronous placement this
        is the stage the train loop pulls from."""
        pulled_from = self.prefetch_to_device <= 0
        return iter(DevicePrefetcher(
            self.host_iter, depth=self.prefetch, timings=self.timings,
            pull_key="decode", name="devicefeed-host",
            pull_span="feed:decode", count_pulls=pulled_from,
            stop_span="feed:stop" if pulled_from else None))

    def _timed_place(self, batch):
        t0 = time.perf_counter()
        with profiler.step("feed:h2d"):
            out = self._place(batch)
        self.timings.add("h2d", time.perf_counter() - t0)
        return out

    def __iter__(self):
        """Placed batches in the host stage's order — through the async
        :class:`DevicePrefetcher` stage when ``prefetch_to_device`` > 0,
        inline otherwise. Same values in the same order either way; the
        async stage only moves the placement off the consumer's critical
        path."""
        # feed:start: an epoch's chain of stage threads is built anew, and
        # the consumer waits for its first batch to come all the way through:
        # the threads' start, the host stage's gather of that ONE batch (its
        # slice of the first part's permutation, drawn whole) and its H2D
        with profiler.step("feed:start"):
            if self.prefetch_to_device <= 0:
                batches = map(self._timed_place, self._host_batches())
            else:
                batches = iter(DevicePrefetcher(
                    self._host_batches(), fn=self._timed_place,
                    depth=self.prefetch_to_device, name="devicefeed-device",
                    count_pulls=True, stop_span="feed:stop"))
            first = next(batches, None)
        if first is not None:
            yield first
            yield from batches
