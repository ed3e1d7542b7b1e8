"""Roofline analysis from dry-run artifacts.

Per (arch x shape x mesh) cell, derive from the compiled program:
    compute   T_c = HLO_FLOPs_per_device / peak_FLOPs      [s]
    memory    T_m = HLO_bytes_per_device / HBM_bw          [s]
    collective T_x = collective_bytes_per_device / link_bw [s]
(cost_analysis / memory_analysis are per-device on the partitioned module —
verified by scaling tests; the spec's global-bytes / (chips*bw) form reduces
to the same per-device quotient.)

Bottleneck = argmax term. `mfu_bound` = MODEL_FLOPS / (chips * peak * T_bound)
with T_bound = max(terms) (perfect-overlap bound): the roofline fraction an
ideal schedule could reach, and the number §Perf hillclimbs.
`useful_ratio` = MODEL_FLOPS / (HLO_FLOPs * chips) flags remat/redundant
compute (XLA counts 2MNK per dot, same convention as 6ND).
"""
from __future__ import annotations


from ..configs.base import SHAPES, get_config

#: Published per-chip peaks keyed by `jax.Device.device_kind` (source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
#: int8, 819 GB/s HBM; ici_bw is the per-link figure the collective term
#: divides by). A kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": dict(peak_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                        ici_bw=50e9),
}

#: The chip the dry-run meshes (a v5e pod slice) are compiled for.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of `device_kind`; ValueError for a kind with no
    published entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None


def param_count(cfg, active_only: bool = False) -> float:
    """Analytical parameter count (active = MoE top-k + shared only)."""
    d, hd = cfg.d_model, cfg.head_dim
    n = 0.0
    if cfg.input_mode == "tokens":
        n += cfg.padded_vocab * d
    if not cfg.tie_embeddings:
        n += d * cfg.padded_vocab * max(cfg.n_codebooks, 1)

    def attn():
        return d * cfg.n_heads * hd * 2 + d * cfg.n_kv_heads * hd * 2

    def mlp(ff):
        return d * ff * (3 if cfg.act == "silu" else 2)

    if cfg.family in ("dense", "vlm", "audio"):
        n += cfg.n_layers * (attn() + mlp(cfg.d_ff) + 2 * d)
    elif cfg.family == "moe":
        fe = cfg.expert_ff or cfg.d_ff
        e_used = cfg.top_k if active_only else cfg.n_experts
        per = (attn() + d * cfg.n_experts          # router
               + e_used * 3 * d * fe
               + cfg.n_shared_experts * 3 * d * fe + 2 * d)
        n += cfg.n_layers * per
    elif cfg.family in ("ssm", "hybrid"):
        di = cfg.d_inner
        proj = 2 * di + 2 * cfg.ssm_ngroups * cfg.ssm_state + cfg.ssm_nheads
        per = d * proj + di * d + cfg.conv_width * (
            di + 2 * cfg.ssm_ngroups * cfg.ssm_state) + di + 2 * d
        n += cfg.n_layers * per
        if cfg.family == "hybrid":
            n += 2 * d * d + attn() + mlp(cfg.d_ff) + 3 * d  # shared block
    return n


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N*D inference; N = active."""
    spec = SHAPES[shape_name]
    tokens = spec["global_batch"] * (1 if spec["kind"] == "decode"
                                     else spec["seq_len"])
    n_active = param_count(cfg, active_only=True)
    mult = 6.0 if spec["kind"] == "train" else 2.0
    return mult * n_active * tokens


def analyse_record(rec: dict):
    if rec.get("status") != "ok":
        return None
    cfg = get_config(rec["arch"])
    chips = rec["n_chips"]
    chip = chip_peaks(rec["device_kind"])
    t_c = rec["flops_per_device"] / chip["peak_flops"]
    t_m = rec["bytes_per_device"] / chip["hbm_bw"]
    t_x = rec["collective_bytes_per_device"] / chip["ici_bw"]
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    t_bound = max(terms.values())
    mf = model_flops(cfg, rec["shape"])
    hlo_total = rec["flops_per_device"] * chips
    return dict(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        chips=chips, compute_s=t_c, memory_s=t_m, collective_s=t_x,
        bottleneck=bottleneck, bound_time_us=t_bound * 1e6,
        model_flops=mf,
        useful_ratio=mf / hlo_total if hlo_total else 0.0,
        mfu_bound=mf / (chips * chip["peak_flops"] * t_bound)
        if t_bound else 0.0,
        hbm_gb=rec["memory"]["tpu_peak_estimate"] / 2 ** 30
        if "tpu_peak_estimate" in rec["memory"]
        else rec["memory"]["peak_estimate"] / 2 ** 30,
    )


def format_table(rows) -> str:
    out = ["# Roofline (per device; v5e: 197 TF/s bf16, 819 GB/s HBM, "
           "50 GB/s ICI link)",
           f"{'arch':20s} {'shape':12s} {'T_comp':>9s} {'T_mem':>9s} "
           f"{'T_coll':>9s} {'bound':>10s} {'MFU_bd':>7s} {'useful':>7s} "
           f"{'HBM GB':>7s}"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        out.append(
            f"{r['arch']:20s} {r['shape']:12s} "
            f"{r['compute_s'] * 1e3:8.2f}ms {r['memory_s'] * 1e3:8.2f}ms "
            f"{r['collective_s'] * 1e3:8.2f}ms {r['bottleneck']:>10s} "
            f"{r['mfu_bound']:7.3f} {r['useful_ratio']:7.3f} "
            f"{r['hbm_gb']:7.2f}")
    return "\n".join(out)
