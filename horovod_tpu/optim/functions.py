"""State-synchronization helpers.

Parity with ``horovod/torch/functions.py``: ``broadcast_parameters``,
``broadcast_optimizer_state``, ``broadcast_object`` -- the rank-0-saves /
everyone-restores idiom used on (re)start and by elastic ``state.sync()``.
"""

from __future__ import annotations

import pickle
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..collectives import eager as _eager
from ..core import process_sets as _ps


def _one_row(out) -> np.ndarray:
    return _eager.one_row(out)


def broadcast_(tree: Any, root_rank: int = 0, *, process_set=None) -> Any:
    """Broadcast every array leaf of a pytree from ``root_rank``.

    Works on replicated host-side values: each worker contributes its copy,
    everyone leaves with root's.  Array leaves are FUSED per dtype into one
    flat buffer and broadcast with a single collective per dtype (the
    fusion-buffer idiom) -- a per-leaf loop would compile one XLA program
    per distinct shape -- hundreds of compiles for a real model.
    Non-array leaves (ints, None, ...) pass through
    :func:`broadcast_object`.
    """
    ps = _ps.get_process_set(process_set)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out_leaves = list(leaves)
    arr_idx = [i for i, leaf in enumerate(leaves)
               if isinstance(leaf, (jax.Array, np.ndarray))
               or hasattr(leaf, "dtype")]
    arr_set = set(arr_idx)
    for i, leaf in enumerate(leaves):
        if i not in arr_set:
            out_leaves[i] = broadcast_object(leaf, root_rank, process_set=ps)
    rows = _eager.broadcast_fused([leaves[i] for i in arr_idx], root_rank,
                                  name="broadcast.tree", process_set=ps)
    for i, row in zip(arr_idx, rows):
        out_leaves[i] = jnp.asarray(row)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def broadcast_parameters(params: Any, root_rank: int = 0, *,
                         process_set=None) -> Any:
    """``hvd.broadcast_parameters`` parity: sync model params from root."""
    return broadcast_(params, root_rank, process_set=process_set)


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0, *,
                              process_set=None) -> Any:
    """``hvd.broadcast_optimizer_state`` parity."""
    return broadcast_(opt_state, root_rank, process_set=process_set)


def broadcast_object(obj: Any, root_rank: int = 0, *,
                     process_set=None) -> Any:
    """Pickle-broadcast an arbitrary Python object from ``root_rank``.

    Two-phase (size then padded payload) so processes with different local
    values agree on buffer shape, as the reference does with its
    size-prefixed byte stream.
    """
    ps = _ps.get_process_set(process_set)
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    size = np.array([len(payload)], dtype=np.int32)
    gsize = int(_one_row(_eager.broadcast(
        _eager.replicated_stack(size, ps), root_rank, process_set=ps))[0])
    buf = np.zeros(gsize, dtype=np.uint8)
    buf[:min(len(payload), gsize)] = payload[:gsize]
    out = _one_row(_eager.broadcast(
        _eager.replicated_stack(buf, ps), root_rank, process_set=ps))
    return pickle.loads(out.tobytes())


def allgather_object(obj: Any, *, name=None, process_set=None) -> list:
    """Gather one picklable object per rank; all ranks receive the
    rank-ordered list (``horovod/torch/functions.py::allgather_object``).

    Byte payloads ride the ragged allgather (sizes exchanged first, like
    the reference's size-prefixed gather); single-controller mode returns
    ``size()`` copies of the local object.
    """
    import io

    ps = _ps.get_process_set(process_set)
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    # One ragged gather is enough: allgatherv exchanges sizes internally,
    # and pickle streams are self-delimiting, so the concatenation splits
    # itself back into per-rank objects.
    data = _eager.allgather_value(payload, name=name, process_set=ps)
    buf = io.BytesIO(np.asarray(data).tobytes())
    out = []
    while buf.tell() < len(buf.getbuffer()):
        out.append(pickle.load(buf))
    return out
