"""One description of a served model's layers.

``ServingEngine`` builds its prefill, its decode step and its
``CacheConfig`` from a :class:`LayerSpec`, never from a model's own
config fields: what kind of attention the model has and, a layer,
whether it reads the whole context or a window of it, what ONE token
holds in a page of each of the cache's two pools, what kind of
feed-forward each layer has, what a SLOT keeps beside its pages (state of the
sequence that no token's page entry holds), whether the head is the
embedding transposed, which of the engine's features the model's
programs do not have, and the functions that build those programs.

A config describes itself through a ``layer_spec()`` method
(:class:`~horovod_tpu.serving.mla_moe.MlaMoeConfig`,
:class:`~horovod_tpu.serving.cca_moe.CcaMoeConfig`,
:class:`~horovod_tpu.serving.loop_dense.LoopDenseConfig`,
:class:`~horovod_tpu.serving.swa_moe.SwaMoeConfig`,
:class:`~horovod_tpu.serving.ssm_hybrid.SsmHybridConfig`,
:class:`~horovod_tpu.serving.eva_dense.EvaDenseConfig`); a
``LlamaConfig`` (a plain dataclass of ``models/transformer.py``) is
described here, by the functions of ``serving/decode.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Optional, Tuple

# The engine's optional features, as ``LayerSpec.unsupported`` names them.
FEATURES = ("tp", "lora", "spec_decode", "kv_compress", "prefill_chunk",
            "prefix_cache", "handoff")


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    attention: str                    # "gqa" | "mla" | "cca"
    # Trailing dims of ONE token's entry in the first and the second pool
    # (``[planes, pages, page_size, *dims]``), and what each holds; the
    # second None: the model keeps one pool.
    page: Tuple[Tuple[int, ...], Optional[Tuple[int, ...]]]
    page_holds: Tuple[str, Optional[str]]
    ffn: Tuple[str, ...]              # a layer: "dense" | "moe"
    tied_head: bool
    max_seq_len: int
    # Which trailing dim of a page entry the ``tp`` axis splits (None:
    # the pools are whole on every chip).
    tp_page_dim: Optional[int]
    # ``prefill(params, tokens, *, dtype, adapters, adapter_id,
    # lora_alpha, past) -> (logits, first_planes, second_planes)``, each
    # ``[planes, batch, t, *page entry]`` (None for a pool not kept);
    # with ``slot_state`` a fourth: ``[planes, batch, slot_state]``, what
    # the slot keeps once the prompt's last token is in.
    prefill: Callable[..., Any]
    # ``build_step(mesh, *, slots, page_size, pages_per_slot, dtype,
    # width, with_lora, lora_alpha, compress) -> ServingDecodeStep``.
    build_step: Callable[..., Any]
    param_specs: Callable[[Any], Any]
    # Feature (one of FEATURES) -> why this model's programs lack it.
    unsupported: Mapping[str, str] = dataclasses.field(default_factory=dict)
    # Device state the decode step carries from round to round beside
    # the pools (donated in, handed back), and where it goes when
    # ``serve`` returns.
    step_state: Callable[[], tuple] = lambda: ()
    publish_state: Callable[[tuple], None] = lambda state: None
    # Names of the model's own whole numbers about the round it ran:
    # the tail of the one int32 vector the step returns last, ``[tokens
    # | finite | tells]`` (``decode.tell_round``), behind every slot's
    # greedy token and finite flag; attributes of ``decode.bookkeep``.
    step_tells: Tuple[str, ...] = ()
    # Values a slot keeps a layer BESIDE its pages (None: pages are all
    # a sequence has) and what they are.  ``PagedKVCache`` holds them as
    # ``[planes, slots, slot_state]``: the prefill hands back the row of
    # the prompt's last token, the decode step (which takes the array
    # after ``active`` and returns it after the pools, donated) rewrites
    # the rows of its live slots, release clears a row.
    slot_state: Optional[int] = None
    slot_state_holds: Optional[str] = None
    # The slot state's own type (None: the pools').  A recurrent state
    # that every round rounds again is kept wider than the rows of a
    # page, which are written once.
    slot_state_dtype: Optional[str] = None
    # Of a slot's row, the leading values a decode round's state update
    # must read and write again WHOLE, a plane (None: all of them): what
    # ``decode.round`` counts as ``state_bytes``.
    slot_state_step: Optional[int] = None
    # Tokens a chunk of the prefill's scan, where the prefill is one
    # (``serve.prefill`` then says its ``scan_chunks``).
    scan_chunk: Optional[int] = None
    # Times a token runs through the layers, over the SAME weights (a
    # looped model; one for every other).  Each pass keeps keys and
    # values of its own: the pools (and the slot state) have ``planes``
    # = ``passes * num_layers`` leading entries, pass ``t`` of layer
    # ``l`` in plane ``t * num_layers + l``.
    passes: int = 1
    # What a layer's attention reads: "full" (every token of the
    # context: its planes grow with the sequence) or "window" (the last
    # ``window`` tokens, the current one among them: its planes keep
    # ``ceil(window / page_size) + 1`` pages a slot, written round and
    # round: 9 at a window of 128 over pages of 16, 257 at 4,096, where
    # a slot's ring grows page by page and short requests never fill
    # it).  None: every layer is "full", and the model describes
    # itself as before there were kinds.  The two kinds live in two
    # groups of planes (``PagedKVCache``), each under a page table of
    # its own: full layer number ``i`` reads plane ``i`` of the pools,
    # window layer number ``j`` plane ``j`` of the window pools.  With a
    # window group the prefill returns one thing more, last: the pair
    # ``(first, second)`` of the window planes' rows ``[window planes,
    # batch, rows, *page entry]``, the prompt's LAST rows
    # (``kvcache.window_rows_from``); its first and second
    # planes are the full layers' alone.  The decode step takes the
    # window group's table after ``active`` and the window pools, donated,
    # before its own state.
    #
    # A third kind, "chunked": the layer reads the window the current
    # token lies in EXACTLY and every ``row_tokens`` tokens of the
    # windows before it through ONE pooled row.  Such a layer has a plane
    # in BOTH groups, its number among the chunked layers in each: its
    # window plane is the ring of exact rows, its growing plane holds a
    # row a chunk.  So that one walk reaches both, the window group's
    # pages then lie in the growing planes' own pools, behind their
    # scratch page (``CacheConfig.window_in_pool``): the step takes the
    # window group's table and no further pool.  The prefill hands back
    # the POOLED rows of the prompt's whole chunks as its first and
    # second planes (``[planes, batch, t // row_tokens, *page entry]``)
    # and the exact rows of its last window as the window pair.
    attn_kinds: Optional[Tuple[str, ...]] = None
    window: Optional[int] = None
    # Tokens ONE row of the growing planes stands for (a chunked layer's
    # pooled row): what prices, reserves and frees the cache reckons a
    # sequence of ``n`` tokens as ``ceil(ceil(n / row_tokens) /
    # page_size)`` growing pages.
    row_tokens: int = 1

    def __post_init__(self):
        if self.attention not in ("gqa", "mla", "cca"):
            raise ValueError(f"attention kind {self.attention!r}")
        if set(self.ffn) - {"dense", "moe"}:
            raise ValueError(f"feed-forward kinds {sorted(set(self.ffn))}")
        kinds = self.attn_kinds
        if kinds is not None and (
                set(kinds) - {"full", "window", "chunked"}
                or len(kinds) != len(self.ffn)
                or not {"full", "chunked"} & set(kinds)):
            raise ValueError(
                f"attention kinds {kinds} of {len(self.ffn)} layers: "
                '"full", "window" or "chunked" a layer, one of them at '
                'least "full" or "chunked"')
        if (self.window is not None) != (
                kinds is not None and bool({"window", "chunked"}
                                           & set(kinds))) \
                or (self.window is not None and self.window < 1):
            raise ValueError(
                f"window {self.window} and attention kinds {kinds}: a "
                "window's length goes with window layers")
        chunked = kinds is not None and "chunked" in kinds
        if chunked != (self.row_tokens > 1) or self.row_tokens < 1 \
                or (chunked and (set(kinds) != {"chunked"}
                                 or self.window % self.row_tokens)):
            raise ValueError(
                f"attention kinds {kinds}, {self.row_tokens} tokens a "
                f"growing row, window {self.window}: chunked layers, all "
                "or none, read a window of whole chunks")
        if self.window is not None and (self.passes != 1
                                        or self.page[1] is None):
            raise NotImplementedError(
                "a window group is built for one pass over two pools: "
                f"{self.passes} passes, pools {self.page}")
        if [e is None for e in self.page] != [
                h is None for h in self.page_holds] or self.page[0] is None:
            raise ValueError(
                f"pools {self.page} and what they hold {self.page_holds}")
        if self.passes < 1:
            raise ValueError(f"passes {self.passes}")
        if (self.slot_state is None) != (self.slot_state_holds is None) \
                or (self.slot_state is not None and self.slot_state < 1):
            raise ValueError(
                f"slot state {self.slot_state} and what it holds "
                f"{self.slot_state_holds!r}")
        step = self.slot_state_step
        if (self.slot_state_dtype is not None or step is not None) \
                and self.slot_state is None \
                or step is not None and not 0 < step <= self.slot_state:
            raise ValueError(
                f"a slot state of {self.slot_state} values, its type "
                f"{self.slot_state_dtype!r}, {self.slot_state_step} of them "
                "rewritten a round")

    @property
    def num_layers(self) -> int:
        """Layers that have weights."""
        return len(self.ffn)

    @property
    def planes(self) -> int:
        """Leading entries of the pools: what sizes, writes, reads,
        frees, re-prefills or ships the cache counts these, never the
        layers.  The full layers' alone, where some are window layers; a
        chunked layer has one here and one in the window group."""
        return self.passes * (len(self.ffn)
                              - (self.attn_kinds or ()).count("window"))

    @property
    def window_planes(self) -> int:
        """Leading entries of the window group's pools."""
        kinds = self.attn_kinds or ()
        return kinds.count("window") + kinds.count("chunked")

    @property
    def window_aligned(self) -> bool:
        """The window is ALIGNED (chunked layers): token ``i`` sees the
        window it lies in, from ``window * (i // window)``, not its last
        ``window`` tokens."""
        return "chunked" in (self.attn_kinds or ())

    def require(self, **wanted: bool) -> None:
        """Raise ``NotImplementedError``, by name, for each feature that
        is wanted and that this model's programs do not have."""
        for name, on in wanted.items():
            if name not in FEATURES:
                raise KeyError(name)
            if on and name in self.unsupported:
                raise NotImplementedError(
                    f"{name}: {self.unsupported[name]}")

    def pool_sharding(self, mesh, tp_axis: str = "tp"):
        """The pools' sharding: the ``tp`` split on ``tp_page_dim``."""
        from .kvcache import cache_sharding
        return cache_sharding(mesh, tp_axis, entry_rank=len(self.page[0]),
                              split=self.tp_page_dim)


def layer_spec(config) -> LayerSpec:
    """The description of ``config``'s layers: its own, or a
    ``LlamaConfig``'s."""
    describe = getattr(config, "layer_spec", None)
    if describe is not None:
        return describe()
    from ..models.transformer import LlamaConfig
    if isinstance(config, LlamaConfig):
        return _llama_spec(config)
    raise TypeError(
        f"{type(config).__name__} has no layer_spec() and is not a "
        "LlamaConfig: ServingEngine cannot build its programs from it")


def _llama_spec(config) -> LayerSpec:
    from . import decode

    def prefill(params, tokens, **kw):
        return decode.prefill_forward(params, config, tokens, **kw)

    def build_step(mesh, *, width: int = 1, **kw):
        if width > 1:
            kw.pop("with_lora", None)
            kw.pop("lora_alpha", None)
            return decode.build_verify_step(config, mesh, width=width, **kw)
        return decode.build_decode_step(config, mesh, **kw)

    # One row a token in each pool, head ``j`` in columns ``j * head_dim
    # .. (j + 1) * head_dim``: the decode step's page walk reads it in
    # place (``ops.attention.cca_decode_attention``).
    entry = (config.num_kv_heads * config.head_dim,)
    return LayerSpec(
        attention="gqa", page=(entry, entry),
        page_holds=("rotated keys", "values"),
        ffn=("dense",) * config.num_layers, tied_head=True,
        max_seq_len=config.max_seq_len, tp_page_dim=0, prefill=prefill,
        build_step=build_step, param_specs=decode.decode_param_specs)
