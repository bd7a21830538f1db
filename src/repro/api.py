"""Public API surface of the PUDTune reproduction.

One import site for the serving stack — the session facade, the typed pack
pytrees, the execution-backend registry, and the placement/config types they
speak.  Workloads should depend on this module; the deeper layers
(``repro.pud``, ``repro.kernels``, ``repro.core``) stay free to refactor.

    from repro.api import PUDSession, PUDGemvConfig

    session = PUDSession.open("qwen3-1.7b", grid=FleetConfig(...),
                              cache_dir="~/.pud-cache")
    session.calibrate()
    packed = session.pack(params, PUDGemvConfig(weight_bits=4))
    y = session.linear(x, "unembed/w", backend="reference")

See docs/api.md for the lifecycle and the old->new call-site migration
table.
"""
from __future__ import annotations

from repro.core.calibrate import CalibrationConfig
from repro.core.canary import CanarySet, drifted_offsets, probe_ecr
from repro.core.fleet import (FleetConfig, load_or_calibrate,
                              recalibrate_subarrays)
from repro.core.reliability import DriftSimulator
from repro.analysis.contracts import check_shard_slices
from repro.kernels.backends import (Backend, backend_names, get_backend,
                                    register_backend)
from repro.pud.gemv import (ATTN_PACKABLE, ECR_BASELINE_B300,
                            ECR_PUDTUNE_T210, FFN_PACKABLE,
                            FleetPerfAggregate, FleetPerfModel,
                            PUDGemvConfig, PUDPerfModel, pack_linear,
                            pud_linear, weight_traffic)
from repro.pud.packed import (LAYOUT_BITPACK, LAYOUT_DENSE, PackedModel,
                              PackedTensor, ShardedPackedTensor,
                              as_packed_tensor, load_packed_npz,
                              packed_bytes, save_packed_npz, to_bitpacked,
                              to_dense)
from repro.pud.packer import (LayerSource, pack_for_serving, pack_model,
                              pack_model_sharded, packing_requests)
from repro.pud.physics import PhysicsParams
from repro.pud.placement import (Placement, PlacementError, PlacementRequest,
                                 TensorPlacement, inject_read_faults,
                                 refresh_fault_state, shard_column_slices)
from repro.runtime.calib_cache import CalibrationTableCache
from repro.runtime.drift import (DriftConfig, DriftController, DriftDetector,
                                 DriftEvent, DriftMonitor, FleetDriftMonitor)
from repro.runtime.engine import (Completion, FleetServingEngine, Request,
                                  ServingEngine, SLOConfig)
from repro.runtime.prefix_cache import PrefixCache
from repro.runtime.session import (CalibrationState, PUDFleetSession,
                                   PUDSession)
from repro.runtime.watchdog import Heartbeat, StepWatchdog

__all__ = [
    # session lifecycle
    "PUDSession", "CalibrationState",
    # sharded multi-device serving fleet
    "PUDFleetSession", "FleetServingEngine", "FleetDriftMonitor",
    "FleetPerfAggregate", "ShardedPackedTensor", "pack_model_sharded",
    "LayerSource",
    "shard_column_slices", "check_shard_slices",
    # batched serving
    "ServingEngine", "Request", "Completion",
    "PrefixCache", "SLOConfig",
    "StepWatchdog", "Heartbeat",
    # drift monitoring + live recalibration
    "DriftMonitor", "DriftController", "DriftDetector", "DriftConfig",
    "DriftEvent", "DriftSimulator", "CanarySet", "probe_ecr",
    "drifted_offsets", "recalibrate_subarrays", "refresh_fault_state",
    # configs
    "PUDGemvConfig", "FleetConfig", "CalibrationConfig", "PhysicsParams",
    "FFN_PACKABLE", "ATTN_PACKABLE",
    # typed packs + storage layouts
    "PackedTensor", "PackedModel", "as_packed_tensor", "packed_bytes",
    "pack_model", "packing_requests",
    "LAYOUT_BITPACK", "LAYOUT_DENSE", "to_bitpacked", "to_dense",
    "save_packed_npz", "load_packed_npz", "weight_traffic",
    # backends
    "Backend", "register_backend", "get_backend", "backend_names",
    # placement
    "Placement", "TensorPlacement", "PlacementRequest", "PlacementError",
    "inject_read_faults",
    # perf models + Table-I operating points
    "PUDPerfModel", "FleetPerfModel",
    "ECR_BASELINE_B300", "ECR_PUDTUNE_T210",
    # persistence + legacy shims
    "CalibrationTableCache", "load_or_calibrate",
    "pack_for_serving", "pack_linear", "pud_linear",
]
