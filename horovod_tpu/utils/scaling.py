"""Scaling evidence: HLO collective accounting + analytic efficiency model.

BASELINE.json's north star is >=90% scaling efficiency from 1 to 256
chips (ResNet-50 and BERT-Large data-parallel).  Without pod hardware
that claim cannot be timed, so this module produces the evidence that CAN
be produced mechanically (SURVEY.md section 6, section 7 hard part 5):

1. **Wire accounting from the compiled program.**  The train step is
   compiled for an n-device mesh and the optimized HLO is parsed for
   collectives: op counts and payload bytes.  Two invariants are
   checkable per model: the per-chip collective bytes match the gradient
   (+ BN-stat) payload the fusion planner predicts, and they are
   INDEPENDENT of n -- the defining property of allreduce data
   parallelism (bytes/chip ~ 2B(n-1)/n -> 2B).  A fusion regression
   (e.g. a gradient leaf escaping the buckets, a stats tree gathering
   instead of reducing) changes these numbers and fails the assertion.
2. **Overlap-capability accounting from the emitted (pre-optimization)
   StableHLO.**  Gradient buckets are emitted as SEPARATE psums whose
   operands depend only on their own slice of the backward pass, which
   is what lets a latency-hiding scheduler start bucket k's allreduce
   while bucket k+1's gradients are still being computed.  The CPU
   backend used for virtual meshes has no latency-hiding scheduler (it
   even re-combines the buckets), so the HLO *schedule* itself is not
   checkable off-TPU; what is checked: the emitted program has the
   planned bucket structure and the compiled module donates the
   parameter buffers (in-place update, no double-buffering stall).
3. **Analytic 1->256 projection.**  Measured single-chip step time +
   measured wire bytes + published link bandwidths -> predicted
   efficiency curve, reported for both the no-overlap (worst-case) and
   full-overlap (best-case) bounds.  All constants and formulas are
   explicit below; change them, the curve moves -- there is no hidden
   calibration.

Reference anchor: the upstream benchmark recipe measures images/s at
1..256 GPUs (SURVEY.md section 6); its scaling efficiency rests on the
same two quantities -- per-rank wire bytes (NCCL ring allreduce moves
2B(n-1)/n) and backward/comm overlap -- that this module accounts for.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# HLO parsing.
# ---------------------------------------------------------------------------

_DT_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "i64": 8, "i32": 4, "i16": 2, "i8": 1,
    "i1": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `f32[128,4]{1,0} all-reduce(...)` or tuple-result variadic forms; -start
# counts once, -done is skipped.
_HLO_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9_]+\[[\d,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([\d,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DT_BYTES:
            continue
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        total += size * _DT_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    """Per-op-kind (count, payload bytes) from one HLO module."""
    counts: Dict[str, int]
    bytes: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def optimized_collective_stats(compiled_text: str) -> CollectiveStats:
    """Count collectives and payload bytes in optimized HLO
    (``jax.jit(f).lower(...).compile().as_text()``).

    Payload bytes are the RESULT shape bytes (for an allreduce the payload
    equals the result; variadic combined all-reduces report the tuple
    total).  ``-done`` halves of async pairs are skipped so a started
    collective counts once.
    """
    counts: Dict[str, int] = {}
    bytes_: Dict[str, int] = {}
    for m in _HLO_OP_RE.finditer(compiled_text):
        shape, op, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue
        counts[op] = counts.get(op, 0) + 1
        bytes_[op] = bytes_.get(op, 0) + _shape_bytes(shape)
    return CollectiveStats(counts=counts, bytes=bytes_)


_STABLE_RE = re.compile(
    r'"stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|'
    r'collective_permute)".*?\)\s*->\s*(\([^)]*\)|tensor<[^>]*>)',
    re.DOTALL)

_TENSOR_RE = re.compile(r"tensor<([^>]*)>")


def _tensor_bytes(t: str) -> int:
    parts = t.split("x")
    dt = parts[-1]
    if dt not in _DT_BYTES:
        return 0
    size = 1
    for d in parts[:-1]:
        size *= int(d)
    return size * _DT_BYTES[dt]


def emitted_collective_stats(lowered_text: str) -> CollectiveStats:
    """Count the collectives OUR trace emitted (pre-XLA-optimization
    StableHLO, ``jax.jit(f).lower(...).as_text()``): one ``all_reduce``
    per fusion bucket, per BN-stat leaf, per loss scalar.  This is the
    structure the latency-hiding scheduler sees; XLA's combiner may later
    merge compatible ops (backend- and threshold-dependent)."""
    counts: Dict[str, int] = {}
    bytes_: Dict[str, int] = {}
    for m in _STABLE_RE.finditer(lowered_text):
        op = m.group(1).replace("_", "-")
        counts[op] = counts.get(op, 0) + 1
        bytes_[op] = bytes_.get(op, 0) + sum(
            _tensor_bytes(t.group(1))
            for t in _TENSOR_RE.finditer(m.group(2)))
    return CollectiveStats(counts=counts, bytes=bytes_)


def has_buffer_donation(compiled_text: str) -> bool:
    """True when the compiled module aliases inputs to outputs (donated
    params/opt-state update in place -- no double-buffered HBM copy)."""
    return "input_output_alias" in compiled_text


# ---------------------------------------------------------------------------
# Compiled-schedule overlap analysis (TPU topology AOT).
# ---------------------------------------------------------------------------
#
# ``jax.experimental.topologies.get_topology_desc(platform="tpu",
# topology_name="v5e:2x4")`` + ``lowered.compile()`` produces a REAL
# scheduled TPU executable with no TPU attached (measured round 4: the
# bundled libtpu compiles deviceless; ``is_scheduled=true`` in the
# module).  The entry computation's instruction order IS the execution
# order, so overlap is mechanically checkable: a collective hides behind
# compute iff it is emitted as an async ``-start``/``-done`` pair with
# compute instructions scheduled inside the window.  Measured capability
# matrix of this toolchain (round 4, v5e/v5p/v6e topologies alike):
# ``collective-permute`` and ``all-gather`` are emitted async;
# ``all-reduce`` and ``reduce-scatter`` are always synchronous (the
# combiner also merges every bucket psum into ONE variadic all-reduce,
# regardless of the async-collective-fusion / latency-hiding-scheduler
# compile options, which this XLA accepts but which change nothing).

_HEAD_RE = re.compile(r"^%([\w.-]+)\s*=")
_START_OP_RE = re.compile(r"\s([a-z-]+)-start\(")
_DONE_RE = re.compile(r"-done\(%([\w.-]+)[,)]")
_SYNC_COLL_RE = re.compile(
    r" (" + "|".join(_COLLECTIVES) + r")\(")
_NAME_SHAPE_RE = re.compile(r"%([\w.-]+) = (\([^)]*\)|\S+) ([a-z-]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _clean_bytes(shape_text: str) -> int:
    """Bytes of a shape string, layout/tiling annotations stripped."""
    return _shape_bytes(re.sub(r"\{[^}]*\}", "", shape_text))


def _shape_dims(shape_text: str):
    m = _SHAPE_RE.search(re.sub(r"\{[^}]*\}", "", shape_text))
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class ScheduleReport:
    """Mechanical overlap evidence from one scheduled TPU module."""
    sync_collectives: list          # (op, payload_bytes, schedule_idx)
    async_collectives: list         # (op, payload_bytes, start_idx, done_idx)
    async_window_seconds: float     # est. compute scheduled inside windows
    total_compute_seconds: float    # est. compute of the whole schedule
    n_instructions: int
    n_devices: int = 0              # mesh size the module was compiled for

    @property
    def sync_bytes(self) -> int:
        return sum(b for _, b, _ in self.sync_collectives)

    @property
    def async_bytes(self) -> int:
        return sum(b for _, b, _, _ in self.async_collectives)

    @staticmethod
    def _eq_payload(ops, n: int) -> float:
        """Result bytes -> EQUIVALENT allreduce payload (the B in
        2B(n-1)/n), so traffic can be projected to other mesh sizes with
        the same ring law.  Per-op result-bytes semantics differ: a
        ``collective-permute`` result is LINK bytes (one hop); an
        ``all-gather``/``all-to-all`` result is the full payload B
        (link B(n-1)/n = HALF an allreduce of the same B); a
        ``reduce-scatter`` result is the B/n shard."""
        if n <= 1:
            return float(sum(b for _, b in ops))
        ring = 2.0 * (n - 1) / n
        eq = 0.0
        for op, b in ops:
            if op in ("all-gather", "all-to-all"):
                eq += b / 2.0
            elif op == "all-reduce":
                eq += b            # result bytes == full payload == B
            elif op == "reduce-scatter":
                eq += b * n / 2.0  # result is B/n shard; link = B(n-1)/n
            else:                  # permute: result bytes ARE link bytes
                eq += b / ring
        return eq

    def async_eq_payload(self) -> float:
        """Async traffic as equivalent allreduce payload.  Requires
        ``n_devices``."""
        return self._eq_payload(
            [(op, b) for op, b, _, _ in self.async_collectives],
            self.n_devices)

    def sync_eq_payload(self) -> float:
        """Sync traffic as equivalent allreduce payload.  Identical to
        ``sync_bytes`` when every sync collective is an all-reduce (the
        usual case); differs once sync all-to-all / all-gather appear
        (e.g. the fp8 exchange codec on a plain-DP config)."""
        return self._eq_payload(
            [(op, b) for op, b, _ in self.sync_collectives],
            self.n_devices)


def _entry_instructions(compiled_text: str):
    """Instruction lines of the ENTRY computation, in schedule order."""
    lines = compiled_text.splitlines()
    out = []
    in_entry = False
    for ln in lines:
        if ln.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry:
            if ln.startswith("}"):
                break
            s = ln.strip()
            if s.startswith(("%", "ROOT ")):
                out.append(s.lstrip("ROOT ").strip())
    return out


def _module_shapes(compiled_text: str):
    """name -> (shape_text, op) for every instruction in the module."""
    shapes = {}
    for m in _NAME_SHAPE_RE.finditer(compiled_text):
        shapes[m.group(1)] = (m.group(2), m.group(3))
    return shapes


def _conv_flops(line: str, shapes) -> float:
    dims_out = _shape_dims(line.split("=", 1)[1])
    ops = re.findall(r"convolution\(%([\w.-]+), %([\w.-]+)\)", line)
    lab = _DIM_LABELS_RE.search(line)
    if not dims_out or not ops or not lab:
        return 0.0
    ker = shapes.get(ops[0][1])
    kdims = _shape_dims(ker[0]) if ker else None
    if not kdims:
        return 0.0
    out_elems = 1
    for d in dims_out:
        out_elems *= d
    kelems = 1
    for d in kdims:
        kelems *= d
    o_pos = lab.group(2).find("o")
    o_size = kdims[o_pos] if 0 <= o_pos < len(kdims) else 1
    return 2.0 * out_elems * kelems / max(o_size, 1)


def _dot_flops(line: str, shapes) -> float:
    dims_out = _shape_dims(line.split("=", 1)[1])
    ops = re.findall(r"dot\(%([\w.-]+), %([\w.-]+)\)", line)
    cm = _CONTRACT_RE.search(line)
    if not dims_out or not ops:
        return 0.0
    lhs = shapes.get(ops[0][0])
    ldims = _shape_dims(lhs[0]) if lhs else None
    if not ldims:
        return 0.0
    out_elems = 1
    for d in dims_out:
        out_elems *= d
    k = 1
    if cm:
        for i in (int(x) for x in cm.group(1).split(",") if x):
            if i < len(ldims):
                k *= ldims[i]
    else:
        k = ldims[-1]
    return 2.0 * out_elems * k


def _computation_flops(compiled_text: str, shapes) -> Dict[str, float]:
    """computation name -> conv+dot FLOPs inside it (fusion bodies)."""
    flops: Dict[str, float] = {}
    current = None
    for ln in compiled_text.splitlines():
        if ln.startswith("%") and ln.rstrip().endswith("{"):
            current = ln.split(" ", 1)[0].lstrip("%")
            flops[current] = 0.0
        elif ln.startswith("}"):
            current = None
        elif current is not None:
            s = ln.strip()
            if " convolution(" in s:
                flops[current] += _conv_flops(s, shapes)
            elif " dot(" in s:
                flops[current] += _dot_flops(s, shapes)
    return flops


_CALLS_RE = re.compile(r"calls=%([\w.-]+)")
_OPERANDS_RE = re.compile(r"\(%([\w.-]+(?:, %[\w.-]+)*)\)")


def _instr_cost_seconds(line: str, shapes, comp_flops,
                        flops_per_s: float, hbm_bytes_per_s: float) -> float:
    """Roofline estimate for one scheduled instruction: max(MXU, HBM)."""
    head, _, tail = line.partition("=")
    name = head.strip().lstrip("%").strip()
    flops = 0.0
    if " fusion(" in line:
        cm = _CALLS_RE.search(line)
        if cm:
            flops = comp_flops.get(cm.group(1), 0.0)
    elif " convolution(" in line:
        flops = _conv_flops(line, shapes)
    elif " dot(" in line:
        flops = _dot_flops(line, shapes)
    elif not any(k in line for k in (" fusion(", " convolution(", " dot(",
                                     " copy(", " transpose(", " reduce(",
                                     " select(", " add(", " multiply(")):
        return 0.0                     # bookkeeping (gte/bitcast/params/...)
    result_bytes = _clean_bytes(tail.split(" ", 2)[1] if tail else "")
    operand_bytes = 0
    om = _OPERANDS_RE.search(line)
    if om:
        for op_name in om.group(1).split(", "):
            sh = shapes.get(op_name.lstrip("%"))
            if sh:
                operand_bytes += _clean_bytes(sh[0])
    return max(flops / flops_per_s,
               (result_bytes + operand_bytes) / hbm_bytes_per_s)


def schedule_overlap_report(
        compiled_text: str, *,
        n_devices: int = 0,
        flops_per_s: float = 0.7 * 197e12,
        hbm_bytes_per_s: float = 0.8 * 819e9) -> ScheduleReport:
    """Parse a SCHEDULED TPU module for collective overlap evidence.

    Defaults model a v5e: MXU at 70% of peak (an earlier runtime's per-op
    reading for convolutional steps, not reproduced), HBM at 80% of
    the 819 GB/s spec.  The estimates only weight schedule POSITIONS --
    the sync/async split itself is exact (it is read off the text).
    """
    entry = _entry_instructions(compiled_text)
    shapes = _module_shapes(compiled_text)
    comp_flops = _computation_flops(compiled_text, shapes)

    starts = {}                      # name -> (op, payload, idx)
    sync, async_ = [], []
    for i, line in enumerate(entry):
        hm = _HEAD_RE.match(line)
        sm0 = _START_OP_RE.search(line)
        if hm and sm0 and sm0.group(1) in _COLLECTIVES:
            starts[hm.group(1)] = (sm0.group(1), i)
            continue
        dm = _DONE_RE.search(line)
        if dm and dm.group(1) in starts:
            op, si = starts.pop(dm.group(1))
            # Payload = the -done result (the actual collective result,
            # matching the sync accounting; the -start result is a
            # bookkeeping tuple of operands+results+semaphores).
            payload = _clean_bytes(line.split("=", 1)[1].split(" ", 2)[1]
                                   if "=" in line else "")
            async_.append((op, payload, si, i))
            continue
        sm = _SYNC_COLL_RE.search(line)
        if sm:
            # Result shape = text between "= " and the op token; TPU
            # layout/tiling annotations (nested parens) are stripped by
            # _clean_bytes, so variadic tuple results total correctly.
            shape_text = line[line.index("=") + 1:sm.start()]
            sync.append((sm.group(1), _clean_bytes(shape_text), i))

    costs = [_instr_cost_seconds(l, shapes, comp_flops,
                                 flops_per_s, hbm_bytes_per_s)
             for l in entry]
    in_window = [False] * len(entry)
    for _, _, si, di in async_:
        for j in range(si + 1, di):
            in_window[j] = True
    return ScheduleReport(
        sync_collectives=sync,
        async_collectives=async_,
        async_window_seconds=sum(c for c, w in zip(costs, in_window) if w),
        total_compute_seconds=sum(costs),
        n_instructions=len(entry),
        n_devices=n_devices)


def predict_efficiency_scheduled(step_seconds: float, report: ScheduleReport,
                                 chip: "ChipSpec",
                                 ns: Tuple[int, ...] = (
                                     1, 2, 4, 8, 16, 32, 64, 128, 256),
                                 bandwidth_derate: float = 1.0):
    """Efficiency from the COMPILED schedule: sync collective time is
    fully exposed; async collective time hides up to the compute the
    scheduler actually placed inside the windows (measured at compile
    n, assumed n-invariant -- per-chip compute is fixed in DP scaling).

    ``bandwidth_derate`` > 1 divides the effective link bandwidth for the
    ASYNC (point-to-point) traffic: a VHDD partner exchange cannot
    provably use all torus links the way a pipelined ring can, so
    headline claims should also be quoted at a pessimistic derate (4x =
    a single link direction) -- if the window still covers the comm
    there, the overlap conclusion is bandwidth-model-independent.
    """
    out = []
    for n in ns:
        t_sync = allreduce_seconds(report.sync_eq_payload(), n, chip)
        t_async = bandwidth_derate * allreduce_seconds(
            report.async_eq_payload(), n, chip)
        exposed = t_sync + max(0.0, t_async - report.async_window_seconds)
        out.append(EfficiencyPoint(
            n=n, comm_seconds=t_sync + t_async,
            eff_no_overlap=step_seconds / (step_seconds + t_sync + t_async),
            eff_full_overlap=step_seconds / (step_seconds + exposed)))
    return out


# ---------------------------------------------------------------------------
# Analytic efficiency model.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Published per-chip numbers (Google Cloud TPU spec sheets) plus the
    one ASSUMED constant (per-chip DCN share), kept explicit."""
    name: str
    bf16_tflops: float         # published peak
    ici_gbps: float            # published aggregate per-chip ICI (both dirs)
    ici_domain_chips: int      # max chips in one ICI domain (pod/slice)
    dcn_gbps_per_chip: float   # ASSUMED: host NIC Gbps / chips per host

    @property
    def ici_allreduce_bytes_per_s(self) -> float:
        """Effective allreduce bandwidth over ICI.

        A bidirectional ring allreduce streams the 2B(n-1)/n wire bytes
        through each chip's links; of the published aggregate (all links,
        both directions) at most HALF is usable in one direction, so the
        model charges ici_gbps/2 -- conservative for 2D/3D torus slices,
        where multi-axis schedules can use more than one ring.
        """
        return self.ici_gbps / 2 / 8 * 1e9

    @property
    def dcn_allreduce_bytes_per_s(self) -> float:
        return self.dcn_gbps_per_chip / 2 / 8 * 1e9


# Published: cloud.google.com/tpu/docs v5e (197 bf16 TFLOP/s, 1600 Gbps
# ICI, 256-chip pod) and v5p (459 bf16 TFLOP/s, 4800 Gbps ICI, 3D torus).
# DCN share assumes a 200 Gbps host NIC across 8 (v5e) / 4 (v5p) chips.
V5E = ChipSpec("v5e", 197.0, 1600.0, 256, 200.0 / 8)
V5P = ChipSpec("v5p", 459.0, 4800.0, 8960, 200.0 / 4)


def ring_allreduce_seconds(nbytes: float, n: int, bw: float) -> float:
    """Ring allreduce wall time: 2B(n-1)/n wire bytes per chip at bw."""
    if n <= 1:
        return 0.0
    return 2.0 * nbytes * (n - 1) / n / bw


def allreduce_seconds(nbytes: float, n: int, chip: ChipSpec) -> float:
    """Allreduce time on n chips: pure ICI within one domain; two-level
    (ICI reduce-scatter -> DCN allreduce on the shard -> ICI allgather,
    the ``build_mesh(hierarchical=True)`` schedule) beyond it."""
    if n <= chip.ici_domain_chips:
        return ring_allreduce_seconds(nbytes, n, chip.ici_allreduce_bytes_per_s)
    s = chip.ici_domain_chips
    g = (n + s - 1) // s               # DCN groups (full slices)
    ici = 2.0 * nbytes * (s - 1) / s / chip.ici_allreduce_bytes_per_s
    dcn = ring_allreduce_seconds(nbytes / s, g,
                                 chip.dcn_allreduce_bytes_per_s)
    return ici + dcn


@dataclasses.dataclass
class EfficiencyPoint:
    n: int
    comm_seconds: float
    eff_no_overlap: float      # worst case: collectives fully exposed
    eff_full_overlap: float    # best case: hidden behind the backward pass


def predict_efficiency(step_seconds: float, wire_bytes: float,
                       chip: ChipSpec, ns: Tuple[int, ...] = (
                           1, 2, 4, 8, 16, 32, 64, 128, 256),
                       backward_fraction: float = 2.0 / 3.0):
    """Efficiency curve for a data-parallel step.

    ``step_seconds``: measured single-chip step time (the compute that
    perfect scaling preserves).  ``wire_bytes``: per-chip collective
    payload from the HLO accounting (the allreduce input bytes B; the
    ring moves 2B(n-1)/n of traffic).  Bounds:

    * no overlap:   eff = step / (step + t_ar)
    * full overlap: eff = step / (step + max(0, t_ar - backward_fraction
      * step)) -- collectives hide behind the backward pass, which is
      ~2/3 of fwd+bwd FLOPs; anything beyond it is exposed.
    """
    out = []
    for n in ns:
        t_ar = allreduce_seconds(wire_bytes, n, chip)
        exposed = max(0.0, t_ar - backward_fraction * step_seconds)
        out.append(EfficiencyPoint(
            n=n, comm_seconds=t_ar,
            eff_no_overlap=step_seconds / (step_seconds + t_ar),
            eff_full_overlap=step_seconds / (step_seconds + exposed)))
    return out
