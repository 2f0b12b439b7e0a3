"""Dry-run machinery: lower + compile every (arch × shape × mesh) cell.

No parameters are ever materialised: ``jax.eval_shape`` traces
``Model.init`` (Param is a registered pytree) so even kimi-k2-1t costs only
metadata. Each cell produces:

* ``compiled.memory_analysis()``  — proves the per-device footprint fits;
* ``compiled.cost_analysis()``    — HLO FLOPs / bytes for the roofline;
* collective bytes parsed from the post-SPMD HLO text (all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute operand
  sizes) — cost_analysis does not expose these.

Import this module only AFTER device count is configured (launch/dryrun.py
sets XLA_FLAGS before any jax import; tests use small emulated meshes).
"""

from __future__ import annotations

import dataclasses
import re
import time

import jax
import jax.numpy as jnp

from ..configs import RunConfig, cell_status, get_config, get_shape
from ..models import build_model, split_params
from ..models.transformer import Model
from ..optim.optimizers import make_optimizer
from ..parallel import sharding as shd
from ..parallel.axes import ShardingRules, sharding_ctx
from ..train.train_step import build_train_step, build_decode_step
from .specs import decode_input_specs, train_input_specs

__all__ = ["run_cell", "default_run_cfg", "CellResult", "HW"]

# TPU v5e constants (assignment §ROOFLINE):
HW = {
    "peak_flops": 197e12,   # bf16 FLOP/s per chip
    "hbm_bw": 819e9,        # bytes/s per chip
    "ici_bw": 50e9,         # bytes/s per link
    "hbm_bytes": 16e9,      # per chip
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")

_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
    "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}


def _tuple_or_operand_bytes(line: str) -> int:
    """Sum array byte-sizes of the *result* of a collective op line."""
    lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split("(", 1)[0]
    total = 0
    for m in _SHAPE_RE.finditer(lhs):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES.get(dt, 4)
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective result bytes by op kind, from post-SPMD HLO."""
    out: dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        b = _tuple_or_operand_bytes(line)
        out[kind] = out.get(kind, 0) + b
        count += 1
    out["total_bytes"] = float(sum(v for k, v in out.items() if k != "num_ops"))
    out["num_ops"] = count
    return out


_CONVERT_RE = re.compile(
    r"=\s*f32\[([\d,]+)\][^=]*(?:fusion|convert)\(%param(?:\.\d+)?\b"
)


def cpu_convert_overhead(hlo_text: str) -> float:
    """Bytes of hoisted bf16->f32 weight converts (CPU-backend artifact).

    XLA:CPU has no native bf16 matmul, so it converts weight parameters to
    f32 and hoists the converts out of the layer scan — inflating temp by
    ~2x params/device. TPU executes bf16 dots natively, so the dry-run
    reports ``temp_tpu_adjusted = temp - this``.
    """
    total = 0.0
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry and line.strip() == "}":
            break
        if not in_entry:
            continue
        m = _CONVERT_RE.search(line)
        if m:
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            total += 4.0 * n
    return total


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str
    step_kind: str = ""
    compile_s: float = 0.0
    flops_per_device: float = 0.0
    bytes_per_device: float = 0.0
    collectives: dict | None = None
    memory: dict | None = None
    param_count: float = 0.0
    error: str = ""
    raw_cost_analysis: dict | None = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def default_run_cfg(arch: str) -> RunConfig:
    """Per-arch RunConfig overrides needed to fit / balance (DESIGN.md §5).

    These are the *baseline* (paper-faithful recipe) settings whose roofline
    is recorded for every cell; the §Perf hillclimb changes them per cell.
    """
    if arch == "kimi-k2-1t-a32b":
        # 1T params on 512 x 16 GB: bf16 params + factored opt WITHOUT an
        # fp32 master (4 TB > global HBM), FSDP everywhere, full remat,
        # sequence-parallel residuals (activations / 16).
        return RunConfig(
            optimizer="adafactor",
            fsdp=True,
            remat="full",
            master_fp32=False,
            seq_parallel=True,
            microbatch=4,
        )
    if arch in ("starcoder2-15b", "llava-next-34b", "phi3-medium-14b", "deepseek-7b"):
        return RunConfig(optimizer="adamw", zero1=True, remat="full", microbatch=8,
                         seq_parallel=True)
    if arch == "deepseek-moe-16b":
        return RunConfig(optimizer="adamw", zero1=True, remat="full", microbatch=8)
    return RunConfig(optimizer="adamw", zero1=True, remat="full", microbatch=4)


def optimized_run_cfg(arch: str) -> tuple[RunConfig, object]:
    """§Perf-optimized (beyond-paper) per-arch configs: (RunConfig, cfg_override).

    Derived from the hillclimb log (EXPERIMENTS §Perf / artifacts/
    perf_iters.jsonl): sub-2B models go pure-DP; 7-34B dense go ZeRO-3+DP;
    MoEs keep EP (kimi via shard_map a2a); zamba additionally tunes the SSD
    chunk. Regenerate the optimized table with
    ``python -m repro.launch.dryrun --optimized``.
    """
    import dataclasses as _dc

    cfg = get_config(arch)
    if arch in ("tinyllama-1.1b", "xlstm-350m", "hubert-xlarge"):
        return RunConfig(zero1=True, remat="dots", parallelism="dp_only"), None
    if arch == "zamba2-1.2b":
        return (
            RunConfig(zero1=True, remat="dots", parallelism="dp_only"),
            _dc.replace(cfg, ssm_chunk=64),
        )
    if arch in ("deepseek-7b", "phi3-medium-14b", "starcoder2-15b", "llava-next-34b"):
        return RunConfig(zero1=True, fsdp=True, remat="full", parallelism="dp_only"), None
    if arch == "deepseek-moe-16b":
        return RunConfig(zero1=True, fsdp=True, remat="full", parallelism="dp_only"), None
    if arch == "kimi-k2-1t-a32b":
        return (
            RunConfig(optimizer="adafactor", fsdp=True, remat="full",
                      master_fp32=False, seq_parallel=True, microbatch=4),
            _dc.replace(cfg, moe_impl="a2a"),
        )
    return default_run_cfg(arch), None


def _abstract_state(model: Model, optimizer):
    params_sds = jax.eval_shape(lambda: model.init(0))
    values_sds, axes = split_params(params_sds)
    opt_sds = jax.eval_shape(optimizer.init, values_sds)
    state_sds = {
        "values": values_sds,
        "opt": opt_sds,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    return state_sds, axes


def _state_shardings(mesh, run_cfg, state_sds, axes, optimizer):
    values_sh = shd.param_shardings(mesh, run_cfg, state_sds["values"], axes)
    opt_sh = shd.opt_state_shardings(
        mesh, run_cfg, state_sds["opt"], optimizer.state_axes(axes)
    )
    return {"values": values_sh, "opt": opt_sh, "step": shd.replicated(mesh)}


def _mesh_name(mesh) -> str:
    return "x".join(f"{k}{v}" for k, v in mesh.shape.items())


def run_cell(
    arch: str,
    shape_name: str,
    mesh,
    *,
    run_cfg: RunConfig | None = None,
    cfg_override=None,
    want_hlo: bool = False,
) -> CellResult | tuple[CellResult, str]:
    """Lower + compile one cell; returns roofline raw terms.

    ``cfg_override`` lets §Perf iterations vary ModelConfig knobs
    (ssm_chunk, attn_chunk, ...) without touching the registry.
    """
    cfg = cfg_override or get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = _mesh_name(mesh)
    status = cell_status(cfg, shape)
    if status != "run":
        return CellResult(arch, shape_name, mesh_name, status)

    run_cfg = run_cfg or default_run_cfg(arch)
    model = build_model(cfg)
    optimizer = make_optimizer(run_cfg)
    rules = ShardingRules(mesh, shd.activation_rules(mesh, run_cfg))
    t0 = time.time()
    try:
        if shape.kind == "train":
            step = build_train_step(model, run_cfg, optimizer)
            state_sds, axes = _abstract_state(model, optimizer)
            state_sh = _state_shardings(mesh, run_cfg, state_sds, axes, optimizer)
            batch_sds = train_input_specs(cfg, shape)
            batch_sh = shd.batch_shardings(mesh, batch_sds, run_cfg)
            with mesh, sharding_ctx(rules):
                lowered = jax.jit(
                    step,
                    in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, shd.replicated(mesh)),
                    donate_argnums=0,
                ).lower(state_sds, batch_sds)
                compiled = lowered.compile()
            step_kind = "train_step"
        elif shape.kind == "prefill":
            state_sds, axes = _abstract_state(model, optimizer)
            values_sds = state_sds["values"]
            values_sh = shd.param_shardings(mesh, run_cfg, values_sds, axes)
            batch_sds = train_input_specs(cfg, shape)
            batch_sh = shd.batch_shardings(mesh, batch_sds, run_cfg)

            def prefill_logits(values, inputs):
                logits, _, _ = model.forward(values, inputs)
                return logits[:, -1:]

            with mesh, sharding_ctx(rules):
                lowered = jax.jit(
                    prefill_logits,
                    in_shardings=(values_sh, batch_sh),
                    out_shardings=shd.replicated(mesh),
                ).lower(values_sds, batch_sds)
                compiled = lowered.compile()
            step_kind = "serve_prefill"
        else:  # decode
            state_sds, axes = _abstract_state(model, optimizer)
            values_sds = state_sds["values"]
            values_sh = shd.param_shardings(mesh, run_cfg, values_sds, axes)
            b = shape.global_batch
            cache_sds = model.cache_specs(b, shape.seq_len)
            cache_rules = ShardingRules(mesh, shd.activation_rules(mesh, run_cfg))
            cache_sh = jax.tree.map(
                lambda sds, ax: cache_rules.sharding_for(ax, sds.shape),
                cache_sds,
                model.cache_axes(b, shape.seq_len, tp=mesh.shape.get("model")),
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            dec_sds = decode_input_specs(cfg, shape)
            dec_sh = shd.batch_shardings(mesh, dec_sds, run_cfg)
            decode = build_decode_step(model)
            with mesh, sharding_ctx(rules):
                lowered = jax.jit(
                    decode,
                    in_shardings=(values_sh, cache_sh, dec_sh["tokens"], dec_sh["cache_pos"]),
                    out_shardings=(shd.replicated(mesh), cache_sh),
                    donate_argnums=1,
                ).lower(
                    values_sds, cache_sds, dec_sds["tokens"], dec_sds["cache_pos"]
                )
                compiled = lowered.compile()
            step_kind = "serve_decode"
    except Exception as e:  # a failing cell is a bug; record it loudly
        return CellResult(
            arch, shape_name, mesh_name, "FAILED", error=f"{type(e).__name__}: {e}"
        )

    compile_s = time.time() - t0
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    memory = {
        k: float(getattr(mem, k, 0.0))
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }
    hlo = compiled.as_text()
    memory["cpu_convert_overhead"] = cpu_convert_overhead(hlo)
    memory["temp_tpu_adjusted"] = max(
        memory["temp_size_in_bytes"] - memory["cpu_convert_overhead"], 0.0
    )
    # Structural costs: cost_analysis() counts while bodies once; hlo_costs
    # multiplies by known_trip_count (exact for scanned layers/chunks).
    from .hlo_cost import hlo_costs

    structural = hlo_costs(hlo)
    coll = {k: float(v) for k, v in structural["coll"].items()}
    coll["total_bytes"] = structural["coll_total"]
    coll["raw_single_body"] = parse_collectives(hlo)["total_bytes"]
    result = CellResult(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        status="ok",
        step_kind=step_kind,
        compile_s=compile_s,
        flops_per_device=float(structural["flops"]),
        # headline: dot-anchored traffic (TPU fusion granularity);
        # upper bound (CPU fusion granularity) kept in memory dict
        bytes_per_device=float(structural["bytes_dots"]),
        collectives=coll,
        memory=memory,
        param_count=float(cfg.param_count()),
    )
    result.memory["bytes_upper_bound"] = float(structural["bytes"])
    result.raw_cost_analysis = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    if want_hlo:
        return result, hlo
    return result
