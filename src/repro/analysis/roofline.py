"""Three-term roofline model for TPU v5e (target hardware; CPU container).

    compute    = HLO_FLOPs / (chips × 197e12 bf16 FLOP/s)
    memory     = HLO_bytes / (chips × 819e9 B/s HBM)
    collective = collective_bytes_per_device / (links × 50e9 B/s ICI)

FLOPs/bytes come from compiled.cost_analysis() (whole-program, all devices);
collective bytes from the post-SPMD HLO text (per-device shapes) — see
analysis/hlo.py. The dominant term approximates the step's lower-bound time;
MODEL_FLOPS/HLO_FLOPs measures how much compiled compute is useful.
"""
from __future__ import annotations

import dataclasses

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link; v5e: ~4 usable links per chip
ICI_LINKS = 4


@dataclasses.dataclass
class Roofline:
    name: str
    mesh: str
    chips: int
    hlo_flops: float         # whole program, summed over devices
    hlo_bytes: float
    coll_bytes: float        # per-device collective output bytes
    model_flops: float = 0.0
    peak_memory_bytes: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (ICI_LINKS * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_frac(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_frac(self) -> float:
        """MFU-like: useful model FLOPs / (chips × peak × bound-time)."""
        denom = self.chips * PEAK_FLOPS * self.t_bound
        return self.model_flops / denom if denom else 0.0

    def row(self) -> dict:
        return dict(
            name=self.name, mesh=self.mesh, chips=self.chips,
            t_compute_s=self.t_compute, t_memory_s=self.t_memory,
            t_collective_s=self.t_collective, bottleneck=self.bottleneck,
            hlo_flops=self.hlo_flops, hlo_bytes=self.hlo_bytes,
            coll_bytes_per_dev=self.coll_bytes,
            model_flops=self.model_flops,
            useful_flop_frac=self.useful_flop_frac,
            roofline_frac=self.roofline_frac,
            peak_memory_gb_per_dev=self.peak_memory_bytes / 1e9)


def model_flops_for(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) for training; 2·N·D for serving."""
    if cfg.family == "lm":
        n = cfg.n_active_params() if cfg.moe else cfg.n_params()
        if shape.kind == "train":
            toks = shape.global_batch * shape.seq_len
            return 6.0 * n * toks
        if shape.kind == "prefill":
            toks = shape.global_batch * shape.seq_len
            return 2.0 * n * toks
        toks = shape.global_batch  # one token per sequence
        return 2.0 * n * toks
    if cfg.family == "gnn":
        # per message: edge MLP + node MLP ≈ 2·(params touched per edge/node)
        from ..launch.specs import gnn_batch_shapes, gnn_dims
        n, e, g = gnn_batch_shapes(cfg, shape)
        d = cfg.d_hidden
        L = cfg.n_layers
        if cfg.kind in ("graphcast", "meshgraphnet"):
            mlp = cfg.mlp_layers
            per_edge = 2 * (3 * d * d + (mlp - 1) * d * d + d * d)
            per_node = 2 * (2 * d * d + (mlp - 1) * d * d + d * d)
            fwd = L * (e * per_edge + n * per_node)
        elif cfg.kind == "egnn":
            fwd = L * e * 2 * (2 * d * d + d * d + d * d)
        else:  # gat
            h = cfg.n_heads
            d_feat, _, d_out, _ = gnn_dims(cfg, shape)
            fwd = 2 * n * d_feat * h * d + 2 * e * h * d \
                + 2 * n * h * d * d_out
        return 3.0 * fwd  # train step ≈ fwd + 2×fwd backward
    # recsys
    from ..launch.specs import input_specs
    ab, _ = input_specs(cfg, shape)
    b = ab["sparse_ids"].shape[0]
    m = cfg.n_sparse + 1
    d = cfg.embed_dim
    cin = sum(2 * b * (hp0 * m) * h * d for hp0, h in
              zip((m,) + cfg.cin_layers[:-1], cfg.cin_layers))
    dims = [m * d] + list(cfg.mlp_dims) + [1]
    mlp = sum(2 * b * a_ * b_ for a_, b_ in zip(dims[:-1], dims[1:]))
    fwd = cin + mlp
    if shape.kind == "retrieval":
        fwd += 2 * ab["candidates"].shape[0] * ab["candidates"].shape[1]
    mult = 3.0 if shape.kind == "train" else 1.0
    return mult * fwd
