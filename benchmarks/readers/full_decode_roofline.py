"""The full-attention layers' decode walk's share of its roofline, in a
model whose other layers see a window only: the accepted
``cca_decode_roofline``'s reader under the name ISSUE 39 gives it.  The
kernel (``hvd_cca_decode``), the family's names (``CCA_DECODE_KERNEL``,
``kv_bytes_per_token``: here the FULL layers' rows alone) and the
arithmetic (``lib/rounds.py:live_bytes_roofline``) are that metric's; its
``workloads`` are pinned to ZAYA's cell by
``tests/benchmark/test_benchmark_zaya.py``, which only a ``benchmark`` PR
may edit: such a PR moves this cell onto that list and drops this file."""

from benchmarks.readers.cca_decode_roofline import read  # noqa: F401
