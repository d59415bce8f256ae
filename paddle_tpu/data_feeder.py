"""Host-side batch assembly: python samples → device-ready Values.

Reference: python/paddle/v2/data_feeder.py:28 (DataFeeder → Arguments) and
py_paddle/dataprovider_converter.py — converts per-slot python data
(dense / sparse / index, with optional sequence nesting per
PyDataProvider2.py:109-250) into the engine's input structures.

TPU-native: everything becomes padded/bucketed numpy, so batch shapes come
from a small fixed set and XLA compiles once per bucket:
- DENSE           -> [b, dim] float32
- INDEX           -> [b] int32
- DENSE seq       -> [b, T] + lengths (T bucketed)
- INDEX seq       -> [b, T] int32 + lengths
- SPARSE_*        -> indices [b, K] + weights [b, K] (K bucketed nonzeros)
- SPARSE_* seq    -> indices [b, T, K] + weights [b, T, K] + lengths
  (reference: sparse_binary_vector_sequence / sparse_float_vector_sequence,
  python/paddle/trainer/PyDataProvider2.py:202,324 — per-timestep sparse
  rows; zero-weight entries are padding so downstream weighted gathers
  are exact without a mask)
"""

from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.ragged import (DEFAULT_BUCKETS, SequenceBatch,
                                    bucket_length, sub_lengths_matrix)
from paddle_tpu.data_type import InputType, Kind, SeqLevel
from paddle_tpu.observe.trace import trace_scope
from paddle_tpu.topology import Value
from paddle_tpu.utils import enforce


class DataFeeder:
    def __init__(self, data_types: Dict[str, InputType],
                 feeding: Dict[str, int] = None, buckets=DEFAULT_BUCKETS):
        """data_types: layer name -> InputType; feeding: name -> index in the
        sample tuple (defaults to declaration order)."""
        self.data_types = data_types
        names = list(data_types)
        self.feeding = feeding or {n: i for i, n in enumerate(names)}
        self.buckets = buckets

    def __call__(self, batch: Sequence) -> Dict[str, Value]:
        return self.feed(batch)

    def _is_prebatched(self, batch) -> bool:
        """True for a tuple of whole-column ndarrays (one per slot, same
        leading batch dim, dense slots with an explicit batch axis) —
        distinguishable from a tuple of per-sample arrays, which fails
        the slot-count or ndim conditions."""
        if not (isinstance(batch, tuple) and batch
                and len(batch) == len(self.data_types)
                and all(isinstance(c, np.ndarray) for c in batch)):
            return False
        lead = set()
        for name, itype in self.data_types.items():
            idx = self.feeding[name]
            if idx >= len(batch):
                return False
            c = batch[idx]
            need = 2 if itype.kind == Kind.DENSE and itype.dim > 1 else 1
            if c.ndim < need:
                return False
            lead.add(c.shape[0])
        return len(lead) == 1

    def feed(self, batch: Sequence) -> Dict[str, Value]:
        feeds = {}
        if self._is_prebatched(batch):
            # pre-batched column arrays (the native batch-assembly path,
            # runtime/loader.dense_batch_reader): one ndarray per slot,
            # consistent leading batch dim, dense columns carrying an
            # explicit batch axis — skip per-sample assembly entirely.
            # (A tuple of per-sample arrays fails the slot-count or ndim
            # checks and falls through to the per-sample path.)
            for name, itype in self.data_types.items():
                col = batch[self.feeding[name]]
                enforce.enforce(
                    itype.kind in (Kind.DENSE, Kind.INDEX)
                    and itype.seq == SeqLevel.NO_SEQUENCE,
                    f"pre-batched feed supports dense/index slots only "
                    f"(slot {name!r})")
                if itype.kind == Kind.INDEX:
                    arr = np.ascontiguousarray(col, dtype=np.int32).reshape(-1)
                    self._check_index_range(arr, itype.dim, name)
                    feeds[name] = Value(jnp.asarray(arr))
                else:
                    feeds[name] = self._dense(col)
            return feeds
        for name, itype in self.data_types.items():
            col = [sample[self.feeding[name]] for sample in batch]
            feeds[name] = self._convert(col, itype, name)
        return feeds

    @staticmethod
    def _dense(rows) -> Value:
        """One dense slot (a list of per-sample rows, or a pre-batched
        column) as a device array, the host's half and the device's
        apart: ``stack`` assembles one contiguous float32 array, ``put``
        hands it to ``jnp.asarray``."""
        with trace_scope("stack"):
            arr = np.ascontiguousarray(rows, dtype=np.float32)
        with trace_scope("put"):
            return Value(jnp.asarray(arr))

    @staticmethod
    def _check_index_range(arr: np.ndarray, dim: int, name: str):
        """Out-of-range ids reach the device as clamped gathers / zero
        one-hots and surface as silent NaNs many layers later (the
        reference's DataProviderConverter validates at the boundary,
        py_paddle/dataprovider_converter.py index scanner) — fail here
        with the slot named instead."""
        if not arr.size:
            return
        mn, mx = int(arr.min()), int(arr.max())
        if mn < 0 or mx >= dim:
            raise ValueError(
                f"input '{name}': index {mn if mn < 0 else mx} out of "
                f"range for dimension {dim}")

    def _convert(self, col: List, itype: InputType, name: str = "?") -> Value:
        if itype.seq == SeqLevel.NO_SEQUENCE:
            if itype.kind == Kind.DENSE:
                return self._dense(col)
            if itype.kind == Kind.INDEX:
                arr = np.asarray(col, np.int32)
                self._check_index_range(arr, itype.dim, name)
                return Value(jnp.asarray(arr))
            return self._sparse(col, itype, name)
        if itype.seq == SeqLevel.SUB_SEQUENCE:
            if itype.kind in (Kind.SPARSE_BINARY, Kind.SPARSE_FLOAT):
                # flatten sub-sequences on the time axis (same layout rule
                # as dense/index level-2) and record the split
                flat = [[ts for sub in subs for ts in sub] for subs in col]
                subl = sub_lengths_matrix(col)
                return self._sparse_seq(flat, itype, name,
                                        sub_lengths=jnp.asarray(subl))
            if itype.kind == Kind.INDEX:
                nested = [[np.asarray(s, np.int32) for s in subs]
                          for subs in col]
                for subs in nested:
                    for a in subs:
                        self._check_index_range(a, itype.dim, name)
                sb = SequenceBatch.from_nested_list(nested, self.buckets)
            else:
                sb = SequenceBatch.from_nested_list(
                    [[np.asarray(s, np.float32) for s in subs] for subs in col],
                    self.buckets)
            return Value(sb.data, sb.lengths, sb.sub_lengths)
        # SEQUENCE
        if itype.kind == Kind.INDEX:
            seqs = [np.asarray(s, np.int32) for s in col]
            for a in seqs:
                self._check_index_range(a, itype.dim, name)
            sb = SequenceBatch.from_list(seqs, self.buckets)
        elif itype.kind == Kind.DENSE:
            sb = SequenceBatch.from_list([np.asarray(s, np.float32) for s in col],
                                         self.buckets)
        else:
            return self._sparse_seq(col, itype, name)
        return Value(sb.data, sb.lengths)

    def _sparse_seq(self, col, itype, name: str = "?",
                    sub_lengths=None) -> Value:
        """Per-timestep sparse rows: each sample is a list over timesteps,
        each timestep a list of indices (binary) or (index, value) pairs.
        Both the time axis and the per-timestep nonzero count are bucketed
        so batch shapes stay in a small compiled set."""
        T = bucket_length(max((len(s) for s in col), default=1),
                          self.buckets)
        K = bucket_length(
            max((len(ts) for s in col for ts in s), default=1),
            self.buckets)
        ids = np.zeros((len(col), T, K), np.int32)
        w = np.zeros((len(col), T, K), np.float32)
        lengths = np.zeros((len(col),), np.int32)
        for i, s in enumerate(col):
            lengths[i] = len(s)
            for t, ts in enumerate(s):
                if itype.kind == Kind.SPARSE_BINARY:
                    idx = list(ts)
                    vals = [1.0] * len(idx)
                else:
                    idx = [p[0] for p in ts]
                    vals = [p[1] for p in ts]
                ids[i, t, : len(idx)] = idx
                w[i, t, : len(vals)] = vals
        self._check_index_range(ids, itype.dim, name)
        return Value(jnp.asarray(ids), jnp.asarray(lengths), sub_lengths,
                     weights=jnp.asarray(w))

    def _sparse(self, col, itype, name: str = "?") -> Value:
        """sparse_binary_vector: sample is a list of indices;
        sparse_float_vector: list of (index, value)."""
        k = bucket_length(max((len(s) for s in col), default=1), self.buckets)
        ids = np.zeros((len(col), k), np.int32)
        w = np.zeros((len(col), k), np.float32)
        for i, s in enumerate(col):
            if itype.kind == Kind.SPARSE_BINARY:
                idx = list(s)
                vals = [1.0] * len(idx)
            else:
                idx = [p[0] for p in s]
                vals = [p[1] for p in s]
            ids[i, : len(idx)] = idx
            w[i, : len(vals)] = vals
        self._check_index_range(ids, itype.dim, name)
        return Value(jnp.asarray(ids), weights=jnp.asarray(w))
