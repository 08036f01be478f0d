"""Three-term roofline analysis from compiled dry-run artifacts.

    compute term    = HLO_FLOPs   / (chips × peak_FLOP/s)
    memory term     = HLO_bytes   / (chips × HBM_bw)
    collective term = coll_bytes  / (chips × link_bw)

HLO figures come from the while-aware parser in ``hlo_analysis`` (XLA's own
``cost_analysis`` counts scan bodies once; see that module). Parsed HLO
shapes are per-chip, so pod totals are parser × chips and the terms reduce
to per-chip figures over per-chip bandwidths — identical algebra, stated
both ways in the report.

Hardware model: one TPU v5e chip (``DEVICE_KIND``), published peaks
(Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s HBM;
~50 GB/s/link ICI. The constants describe that device only: a run on
another device kind must not price itself with them (``check_device_kind``).
"""
from __future__ import annotations

import dataclasses
import math

from repro.configs.base import ArchConfig, InputShape
from repro.core.hlo_analysis import HloCost

DEVICE_KIND = "TPU v5 lite"   # jax's device_kind for a v5e chip
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link (one effective link per phase)

# energy model constants (per chip, activity-based; cf. DESIGN.md §2)
P_IDLE_W = 80.0
P_PEAK_W = 350.0

# host-side cost of one decode dispatch (executable launch + sync +
# scheduler bookkeeping) — the per-token overhead the fused chunk decode
# amortises; edge-class hosts sit around 10⁻⁴ s
DISPATCH_OVERHEAD_S = 1e-4


def check_device_kind(kind: str) -> None:
    """Raise unless the peak constants above describe ``kind`` (a
    ``jax.Device.device_kind``)."""
    if kind != DEVICE_KIND:
        raise ValueError(f"roofline peaks describe {DEVICE_KIND!r}, not "
                         f"{kind!r}: add that device's published peaks "
                         "before pricing work on it")


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-chip raw terms
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    # seconds
    t_compute: float
    t_memory: float
    t_collective: float
    # derived
    dominant: str
    step_time: float
    model_flops: float          # 6·N_active·D (pod-global)
    hlo_flops_total: float      # parser flops × chips
    useful_ratio: float         # model_flops / hlo_flops_total
    collectives_by_kind: dict
    # energy
    utilization: float
    power_w_per_chip: float
    energy_j: float

    def row(self) -> str:
        return (f"| {self.arch} | {self.shape} | {self.mesh} | "
                f"{self.t_compute*1e3:.2f} | {self.t_memory*1e3:.2f} | "
                f"{self.t_collective*1e3:.2f} | **{self.dominant}** | "
                f"{self.useful_ratio:.2f} | {self.energy_j:.1f} |")


def model_flops(cfg: ArchConfig, shape: InputShape) -> float:
    """6·N·D for training, 2·N·D for inference (fwd only), N = active params.

    D = tokens processed this step: B×S for train/prefill, B for decode.
    Encoder-decoder archs process the encoder's frame tokens with the
    encoder params separately (and not at all during decode).
    """
    n = cfg.active_param_count()
    n_enc = 0
    if cfg.n_encoder_layers:
        n_enc = cfg._encoder_layer_params() * cfg.n_encoder_layers
        n -= n_enc
    factor = 6.0 if shape.kind == "train" else 2.0
    if shape.kind == "decode":
        return factor * n * shape.global_batch       # encoder not rerun
    d_dec = shape.global_batch * shape.seq_len
    d_enc = shape.global_batch * cfg.encoder_seq
    return factor * (n * d_dec + n_enc * d_enc)


def prefill_flops(cfg: ArchConfig, n_tokens: int,
                  hit_tokens: int = 0) -> float:
    """Forward FLOPs of one prefill: ``2·N_active`` per token actually
    executed. ``hit_tokens`` is the prefix-cache hit length — those
    positions are served from cached K/V and never enter the prefill
    dispatch, so they cost nothing here (the benchmark's FLOPs-saved
    accounting; cached pages still charge HBM, see
    ``containers.feasible``'s ``prefix_cached_blocks``)."""
    return 2.0 * cfg.active_param_count() * max(n_tokens - hit_tokens, 0)


def decode_step_seconds(cfg: ArchConfig, batch: int = 1, *,
                        context_tokens: int = 0) -> float:
    """Device seconds of ONE decode iteration (the roofline max of its
    compute and memory terms): a batch-``batch`` step streams the
    weights once and computes ``2·N_active·B`` FLOPs;
    ``context_tokens`` adds the per-step KV-cache read. This is the
    per-token quantum both ``decode_chunk_tokens`` (amortisation) and
    the scheduler's SLO chunk cap (admission-latency bound) price."""
    flops = 2.0 * cfg.active_param_count() * batch
    bytes_ = 2.0 * cfg.param_count()          # bf16 weight stream per step
    if context_tokens:
        from repro.core.containers import kv_cache_bytes_per_token
        bytes_ += batch * context_tokens * kv_cache_bytes_per_token(
            cfg, max_len=context_tokens)
    return max(flops / PEAK_FLOPS, bytes_ / HBM_BW)


def decode_chunk_tokens(cfg: ArchConfig, batch: int = 1, *,
                        overhead_s: float = DISPATCH_OVERHEAD_S,
                        overhead_frac: float = 0.1,
                        max_chunk: int = 32,
                        context_tokens: int = 0) -> int:
    """Decode chunk length from arithmetic intensity: the cost-model hook
    the serving engine (and the adaptive scheduler's wave sizing) use.

    A batch-``batch`` decode step streams the weights once and computes
    ``2·N_active·B`` FLOPs, so its device time is the roofline max of the
    compute and memory terms; decode sits far below the machine balance
    point, so per-step *dispatch* overhead, not the device, dominates
    small models. Pick the smallest chunk that keeps the per-chunk
    dispatch overhead under ``overhead_frac`` of fused device time,
    clamped to ``[1, max_chunk]`` (compile cost and admission latency
    bound the top).

    ``context_tokens > 0`` adds the KV-cache stream to the memory term:
    a paged engine runs dozens of in-flight sequences, so each decode
    step also reads up to ``batch × context × bytes/token`` of cache —
    at high concurrency that, not the weights, is what the chunk has to
    amortise the dispatch against.
    """
    t_tok = decode_step_seconds(cfg, batch, context_tokens=context_tokens)
    amortised = overhead_s * (1.0 - overhead_frac) / overhead_frac
    return max(1, min(max_chunk, math.ceil(amortised / max(t_tok, 1e-12))))


def build_report(arch: str, shape: InputShape, cfg: ArchConfig,
                 mesh_desc: str, chips: int, cost: HloCost) -> RooflineReport:
    t_c = cost.flops_per_chip / PEAK_FLOPS
    t_m = cost.bytes_per_chip / HBM_BW
    t_x = cost.coll_wire_bytes_per_chip / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dominant = max(terms, key=terms.get)
    step = max(t_c, t_m, t_x)
    mf = model_flops(cfg, shape)
    hlo_total = cost.flops_per_chip * chips
    util = t_c / step if step > 0 else 0.0
    power = P_IDLE_W + (P_PEAK_W - P_IDLE_W) * util
    energy = chips * power * step
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_desc, chips=chips,
        flops_per_chip=cost.flops_per_chip,
        bytes_per_chip=cost.bytes_per_chip,
        coll_bytes_per_chip=cost.coll_wire_bytes_per_chip,
        t_compute=t_c, t_memory=t_m, t_collective=t_x,
        dominant=dominant, step_time=step,
        model_flops=mf, hlo_flops_total=hlo_total,
        useful_ratio=mf / hlo_total if hlo_total else 0.0,
        collectives_by_kind=cost.collectives,
        utilization=util, power_w_per_chip=power, energy_j=energy)


HEADER = ("| arch | shape | mesh | t_comp (ms) | t_mem (ms) | t_coll (ms) "
          "| dominant | useful | energy (J) |\n"
          "|---|---|---|---|---|---|---|---|---|")
