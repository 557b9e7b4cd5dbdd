"""Serving data plane: continuous-batching multi-host inference.

A new CLIENT of the existing exchange stack, not a parallel universe:
the tensor-parallel decode step routes its activation collectives
through ``collectives/ops.py`` (fusion planner / span recorder / static
auditor all see them), per-request lifecycle lands in the PR 6
MetricsRegistry, and per-leg decode time is attributed by the PR 9
span layer exactly like training time.  On top sits the SLO-driven
control plane (``controlplane``/``policy``): autoscale, graceful drain,
and straggler eviction closed-loop over the same elastic resize path
the training loop uses.
"""

from .controlplane import (ControlPlaneReport,  # noqa: F401
                           FleetScaler, ServingControlPlane)
from .decode import (build_decode_step, build_verify_step,  # noqa: F401
                     decode_param_specs, greedy_sample, prefill_forward,
                     stack_adapters, ServingDecodeStep)
from .engine import (RequestPrefetcher, ServingEngine,  # noqa: F401
                     ServingReport)
from .fleet import (DecodeWorker, FleetReport,  # noqa: F401
                    HandoffTicket, PrefillWorker, ServingFleet)
from .kvcache import (CacheConfig, PagedKVCache,  # noqa: F401
                      PrefixCache, cache_sharding)
from .kvwire import (WirePages, decode_kv, encode_kv,  # noqa: F401
                     import_pages, wire_tier)
from .layerspec import LayerSpec, layer_spec  # noqa: F401
from .loadgen import (LoadSpec, fleet_spec, generate,  # noqa: F401
                      long_prompt_spec, prefix_spec)
from .cca_moe import CcaMoeConfig  # noqa: F401
from .eva_dense import EvaDenseConfig  # noqa: F401
from .loop_dense import LoopDenseConfig  # noqa: F401
from .mla_moe import MlaMoeConfig  # noqa: F401
from .ssm_hybrid import SsmHybridConfig  # noqa: F401
from .swa_moe import SwaMoeConfig  # noqa: F401
from .policy import (Decision, FleetPolicy,  # noqa: F401
                     FleetPolicyConfig, FleetSample, PolicyConfig,
                     ScalePolicy, SLOSample, valid_tp_sizes)
from .router import FleetRouter  # noqa: F401
from .scheduler import (ContinuousBatchScheduler, Request,  # noqa: F401
                        TenantClass, parse_tenant_classes)
from .spec import ModelDrafter, NgramDrafter  # noqa: F401
