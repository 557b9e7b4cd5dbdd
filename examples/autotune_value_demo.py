"""Autotune value demo: the tuner discovers the two-level exchange with
fp8 on the DCN hop when the link budget rewards it -- and rejects it when
it doesn't.

The autotuner's job (SURVEY.md 5.6, ``ParameterManager``) is to pick
exchange knobs the user would otherwise hand-tune per topology.  This
demo makes that value visible WITHOUT a physical two-level pod: an
8-device virtual mesh is built as a (2 dcn x 4 ici) two-level topology
(opening the hierarchical axis), the per-leg DCN codec axis is opted in
(``HOROVOD_AUTOTUNE_HIER=1``), and each sampled configuration is "timed"
by the per-link bandwidth model the autotune module exposes
(:func:`horovod_tpu.autotune.modeled_exchange_seconds`) instead of a wall
clock -- an analytic ring/tree cost:

* flat allreduce moves ``2 (n-1)/n * bytes`` over the SLOWEST link the
  flat ring crosses (a flat ring over a two-level topology is throttled
  by its inter-island hops);
* hierarchical moves the FULL payload over ICI (``2 (g-1)/g * bytes``,
  full precision) and only the ``bytes/g`` shard over DCN
  (``2 (d-1)/d``), with the sampled DCN-leg codec scaling just that
  hop's wire bytes (bf16/fp16 = 1/2, fp8 = 1/4) and paying a fixed
  quantize cost per step, plus one extra phase launch per leg.

Two scenarios bracket the decision:

* ``contended_dcn``   -- 97 MiB gradients (RN50-scale), 40 GB/s ICI vs
  1 GB/s DCN: the cross-slice wire dominates, so the tuner should lock
  hierarchical=1 + fp8-on-DCN (the cheapest wire bytes over the slow
  tier);
* ``uniform_fast``    -- 4 MiB gradients, every link 40 GB/s, quantize
  5 ms: the wire is nearly free, so the codec's quantize cost and the
  second phase launch can only LOSE -- the tuner should lock
  hierarchical=0 + no codec.

The cold-start tuner (no warm-start log) samples the 5-config grid
(flat, plus hier x 4 DCN codecs -- the grid prunes DCN codecs without
the hierarchical schedule) exhaustively and locks the modeled winner in
each scenario.  ``python examples/autotune_value_demo.py`` prints the
selections and, where ``AUTOTUNE_DEMO_OUT`` names a path, writes them
with the full modeled cost table there as JSON;
``tests/test_autotune.py`` asserts the selections.
"""

import json
import os
import sys as _sys
from os.path import abspath as _abs, dirname as _dir

_sys.path.insert(0, _dir(_dir(_abs(__file__))))  # repo root importable

_MiB = 1024 * 1024

SCENARIOS = {
    "contended_dcn": {
        "payload_bytes": 97 * _MiB,
        "ici_bw": 40e9,          # bytes/s per link
        "dcn_bw": 1e9,
        "quantize_s": 0.0005,    # cheap on-chip cast
        "phase_overhead_s": 0.0002,
        "expect": {"hierarchical": 1, "codec": "fp8"},
    },
    "uniform_fast": {
        "payload_bytes": 4 * _MiB,
        "ici_bw": 40e9,
        "dcn_bw": 40e9,
        "quantize_s": 0.005,     # dominates a ~0.2 ms wire
        "phase_overhead_s": 0.0002,
        "expect": {"hierarchical": 0, "codec": "none"},
    },
}

DCN_GROUPS, ICI_GROUP = 2, 4   # the (2, 4) virtual two-level mesh

_CODEC_SCALE = {"none": 1.0, "bf16": 0.5, "fp16": 0.5, "fp8": 0.25}


def codec_name(compression) -> str:
    """Map a Compression codec (or None = configured default) to the
    demo's scale-table key.  Per-leg composites report their DCN leg --
    that is the hop the bandwidth model prices the codec on."""
    if compression is None:
        return "none"
    if getattr(compression, "wire_format", "") == "hier_legs":
        compression = compression.dcn
    name = compression.__name__.lower()
    for k in ("bf16", "fp16", "fp8"):
        if k in name:
            return k
    return "none"


def modeled_step_seconds(hierarchical: bool, codec: str, sc: dict) -> float:
    """Analytic exchange time for one step under the scenario's links.

    ``codec`` is the DCN-leg codec for hierarchical configurations (the
    ICI legs stay full precision -- the real exchange's per-leg
    contract) and the whole-exchange codec for flat ones.
    """
    from horovod_tpu.autotune import modeled_exchange_seconds
    scale = _CODEC_SCALE[codec]
    quant = sc["quantize_s"] if codec != "none" else 0.0
    if hierarchical:
        return modeled_exchange_seconds(
            sc["payload_bytes"], n_dcn=DCN_GROUPS, n_ici=ICI_GROUP,
            hierarchical=True, ici_bw=sc["ici_bw"], dcn_bw=sc["dcn_bw"],
            ici_wire_scale=1.0, dcn_wire_scale=scale, quantize_s=quant,
            phase_overhead_s=sc["phase_overhead_s"])
    return modeled_exchange_seconds(
        sc["payload_bytes"], n_dcn=DCN_GROUPS, n_ici=ICI_GROUP,
        hierarchical=False, ici_bw=sc["ici_bw"], dcn_bw=sc["dcn_bw"],
        ici_wire_scale=scale, quantize_s=quant,
        phase_overhead_s=sc["phase_overhead_s"])


def cost_table(sc: dict) -> dict:
    return {f"hier{h}_{c}": round(modeled_step_seconds(bool(h), c, sc) * 1e3,
                                  3)
            for h in (0, 1) for c in ("none", "bf16", "fp16", "fp8")}


def run_scenario(name: str) -> dict:
    """Cold-start tune under the scenario's injected link model; returns
    the locked selection."""
    from horovod_tpu.autotune import Autotuner, _mesh_is_two_level
    from horovod_tpu.core.config import Config

    sc = SCENARIOS[name]
    assert _mesh_is_two_level(), \
        "run_scenario needs an initialized (dcn, ici) mesh"
    os.environ["HOROVOD_AUTOTUNE_HIER"] = "1"
    try:
        # One pinned threshold x pinned cycle x {flat, hier x 4 DCN
        # codecs}: a 5-config grid sampled exhaustively (max_samples=5).
        # The cycle axis is pinned explicitly -- the tuner otherwise
        # widens it whenever the torch shim is resident in the process
        # (e.g. under a full pytest collection), and a wider grid would
        # outrun the exhaustive sample budget.
        cfg = Config(autotune=True)
        tuner = Autotuner(cfg, steps_per_sample=1,
                          candidates=[64 * _MiB], max_samples=5,
                          cycle_candidates=[cfg.cycle_time])
        assert tuner.tunes_hier_codec
        assert len(tuner.grid) == 5, len(tuner.grid)
        guard = 0
        while not tuner.done and guard < 100:
            t = modeled_step_seconds(
                tuner.hierarchical_explicit(),
                codec_name(tuner.compression_override(None)), sc)
            tuner.record_step(t, sc["payload_bytes"])
            guard += 1
        assert tuner.done, "tuner failed to lock within the guard budget"
    finally:
        del os.environ["HOROVOD_AUTOTUNE_HIER"]
    picked = {"hierarchical": int(tuner.hierarchical_explicit()),
              "codec": codec_name(tuner.compression_override(None))}
    return {"scenario": name,
            "selected": picked,
            "expected": sc["expect"],
            "matches_model_optimum": picked == sc["expect"],
            "sampled_configs": len(tuner._samples),
            "modeled_ms": cost_table(sc)}


def main():
    from horovod_tpu.utils.platform import force_host_device_count
    force_host_device_count(8, cpu=True)
    import jax
    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(jax.devices()[:8], hierarchical=True, dcn_size=2)
    hvd.init(mesh=mesh)
    results = [run_scenario(name) for name in SCENARIOS]
    out_path = os.environ.get("AUTOTUNE_DEMO_OUT")
    if out_path:
        doc = {"demo": "autotune_value_demo",
               "mesh": f"virtual ({DCN_GROUPS}, {ICI_GROUP}) two-level",
               "results": results}
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    for r in results:
        print(f"{r['scenario']}: selected {r['selected']} "
              f"(expected {r['expected']}) -- "
              f"{'OK' if r['matches_model_optimum'] else 'MISMATCH'}",
              flush=True)
    if not all(r["matches_model_optimum"] for r in results):
        return 1
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    _sys.exit(main())
