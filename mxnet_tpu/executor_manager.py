"""Executor-manager helpers (reference ``python/mxnet/executor_manager.py``).

``_split_input_slice`` implements the reference's workload split of a batch
across a context list.  On TPU a "context list" is a view over mesh devices;
the Module's fused path shards the batch dimension instead of slicing it,
but the slice math is kept for API/test parity and for CPU-mesh runs.
"""
from __future__ import annotations

import logging

import numpy as np

from .base import MXNetError
from .ndarray import zeros
from . import ndarray as nd


def _split_input_slice(batch_size, work_load_list):
    """Split a batch into slices proportional to work_load_list
    (reference contract ``executor_manager.py:15-41``)."""
    total = sum(work_load_list)
    shares = [round(batch_size * w / total) for w in work_load_list]
    shortfall = batch_size - sum(shares)
    if shortfall > 0:
        shares[-1] += shortfall     # rounding remainder goes last
    slices = []
    end = 0
    for share in shares:
        begin = int(min(end, batch_size))
        end = int(min(begin + share, batch_size))
        if begin >= end:
            raise MXNetError("Too many slices. Some splits are empty.")
        slices.append(slice(begin, end))
    return slices


def _check_arguments(symbol):
    """Assert no duplicated argument/aux names
    (reference ``executor_manager.py:44-69``)."""
    arg_set = set()
    arg_names = symbol.list_arguments()
    for name in arg_names:
        if name in arg_set:
            raise ValueError("Find duplicated argument name \"%s\"" % name)
        arg_set.add(name)
    aux_set = set()
    for name in symbol.list_auxiliary_states():
        if name in aux_set:
            raise ValueError("Find duplicated auxiliary param name \"%s\"" % name)
        aux_set.add(name)


def _load_general(data, targets):
    """Scatter batch arrays into per-executor slices
    (reference ``executor_manager.py:72-88``)."""
    for d_src, d_targets in zip(data, targets):
        if isinstance(d_targets, nd.NDArray):
            d_src.copyto(d_targets)
        elif isinstance(d_src, nd.NDArray):
            # slice on-device (XLA slice): no host round trip per batch
            n_src = int(d_src.shape[0]) if d_src.shape else 0
            for slice_idx, d_dst in d_targets:
                if (d_src.dtype == d_dst.dtype
                        and tuple(d_src.shape) == tuple(d_dst.shape)
                        and d_src.context == d_dst.context
                        and slice_idx.indices(n_src) == (0, n_src, 1)):
                    # single-executor fast path: whole batch, same dtype
                    # and device — adopt the buffer, zero dispatched ops
                    d_dst._set_data(d_src.data)
                    continue
                piece = d_src.data[slice_idx].astype(d_dst.dtype)
                if tuple(piece.shape) != tuple(d_dst.shape):
                    raise MXNetError(
                        "array shape do not match the shape of NDArray: "
                        "%s vs %s" % (piece.shape, d_dst.shape))
                if d_dst.context != d_src.context:
                    piece = nd._place(piece, d_dst.context)
                d_dst._set_data(piece)
        else:
            src = np.asarray(d_src)
            for slice_idx, d_dst in d_targets:
                d_dst._sync_copyfrom(src[slice_idx])


def _load_data(batch, targets):
    _load_general(batch.data, targets)


def _load_label(batch, targets):
    _load_general(batch.label, targets)


class DataParallelExecutorGroup(object):
    """Re-exported from module.executor_group for backwards compatibility."""

    def __new__(cls, *args, **kwargs):
        from .module.executor_group import DataParallelExecutorGroup as G
        return G(*args, **kwargs)
