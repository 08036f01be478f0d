"""Role-aware sharding rules for params, optimizer state, caches, batches.

Baseline ("paper-faithful container") strategy:
  * inference — Megatron-style tensor parallelism over "model" (q-heads /
    ff / experts on their parallel dims), weights replicated over
    "data"/"pod": the data axis is the *container* axis (independent
    replicas — DESIGN.md §2). Big models additionally FSDP-shard weights
    over "data" (``fsdp=True``) to fit HBM; the extra all-gathers show up
    honestly in the collective roofline term.
  * train — FSDP: weights/optimizer state sharded over "data" on a second
    dim; batch over ("pod","data").

Rules are PATH-BASED (matched on the param-tree key names), not size
heuristics: size heuristics mis-shard attention projections (e.g. sharding
head_dim — a contraction dim — forces a per-tile all-reduce of attention
scores). Every assignment checks divisibility; axes that don't divide are
dropped (GSPMD rejects uneven explicit shardings).

Cache rules (decode): batch over "data" when it divides; kv-heads over
"model" when they divide, otherwise the *sequence* dim goes to "model"
(sequence-parallel flash-decode — each chip owns a slice of the KV cache
and the partial-softmax merge is a small stats collective). When batch
can't use "data" (long_500k has batch 1), the sequence dim is sharded over
"data" instead, so a 500k-token cache spreads over the whole pod.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import mesh_axis_size


def _path_names(path) -> list[str]:
    return [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]


def _ok(shape, i, size) -> bool:
    """Can dim i (negative index from the right) shard over an axis of
    ``size``?"""
    d = shape[i]
    return size > 1 and d % size == 0 and d >= size


def _assemble(shape, rev_assign: dict[int, Any]) -> P:
    """rev_assign keys are negative dim indices."""
    n = len(shape)
    parts = [None] * n
    for i, ax in rev_assign.items():
        if ax is not None:
            parts[n + i] = ax
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


class ShardingRules:
    def __init__(self, mesh, train: bool = False, fsdp: bool | None = None,
                 decode: bool = False):
        """``fsdp=None`` → FSDP iff training. Inference callers pass
        ``fsdp=True`` when model-only weight sharding would overflow HBM.
        ``decode=True`` switches FSDP'd experts to 2D ff-sharding: decode
        activations are tiny, so gathering the token batch beats gathering
        the expert weights every step (§Perf — mixtral decode)."""
        self.mesh = mesh
        self.train = train
        self.fsdp = train if fsdp is None else fsdp
        self.decode = decode
        self.model = mesh_axis_size(mesh, "model")
        self.data = mesh_axis_size(mesh, "data")
        self.pod = mesh_axis_size(mesh, "pod")
        self.batch_axes = (("pod", "data") if self.pod > 1 else ("data",))
        self.data_total = self.data * self.pod

    @property
    def device_set(self) -> frozenset:
        """The devices this rules instance places onto (empty for abstract
        meshes) — a container pool checks these are pairwise disjoint."""
        try:
            devs = self.mesh.devices
        except ValueError:              # an AbstractMesh has no devices
            return frozenset()
        return frozenset(devs.flat)

    # ------------------------------------------------------------------
    def _ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _f(self, shape, i) -> str | None:
        """FSDP axis for dim i if enabled and divisible."""
        return "data" if (self.fsdp and _ok(shape, i, self.data)) else None

    def _m(self, shape, i) -> str | None:
        return "model" if _ok(shape, i, self.model) else None

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def _param_spec(self, path: list[str], shape: tuple) -> P:
        name = path[-1] if path else ""
        ctx = set(path)
        M, f = self._m, self._f

        if name == "table":                       # embed (V, d)
            return _assemble(shape, {-2: M(shape, -2), -1: f(shape, -1)})
        if "lm_head" in ctx:                      # (d, V)
            return _assemble(shape, {-2: f(shape, -2), -1: M(shape, -1)})
        if name == "wq":                          # (d, H, hd)
            return _assemble(shape, {-3: f(shape, -3), -2: M(shape, -2)})
        if name in ("wk", "wv"):                  # (d, kv, hd)
            return _assemble(shape, {-3: f(shape, -3), -2: M(shape, -2)})
        if name == "wo":                          # (H, hd, d)
            return _assemble(shape, {-3: M(shape, -3), -1: f(shape, -1)})
        if name in ("w_uk", "w_uv"):              # MLA up (r, H, dk)
            return _assemble(shape, {-3: f(shape, -3), -2: M(shape, -2)})
        if name == "w_dkv":                       # MLA down (d, r+dr)
            return _assemble(shape, {-2: f(shape, -2)})
        if name in ("w_gate", "w_up"):            # (d, ff) / experts (E, d, ff)
            if "experts" in ctx and len(shape) >= 3:
                if _ok(shape, -3, self.model):    # expert-parallel
                    return _assemble(shape, {-3: "model",
                                             -2: f(shape, -2)})
                if self.decode and self.fsdp \
                        and _ok(shape, -1, self.data * self.model):
                    return _assemble(shape, {-1: ("data", "model")})
                return _assemble(shape, {-2: f(shape, -2),
                                         -1: M(shape, -1)})
            return _assemble(shape, {-2: f(shape, -2), -1: M(shape, -1)})
        if name == "w_down":                      # (ff, d) / experts (E, ff, d)
            if "experts" in ctx and len(shape) >= 3:
                if _ok(shape, -3, self.model):
                    return _assemble(shape, {-3: "model",
                                             -1: f(shape, -1)})
                if self.decode and self.fsdp \
                        and _ok(shape, -2, self.data * self.model):
                    return _assemble(shape, {-2: ("data", "model")})
                return _assemble(shape, {-2: M(shape, -2),
                                         -1: f(shape, -1)})
            return _assemble(shape, {-2: M(shape, -2), -1: f(shape, -1)})
        if name == "router":                      # (d, E)
            return _assemble(shape, {-2: f(shape, -2)})
        if name == "in_proj":                     # mamba (d, d_in_proj)
            return _assemble(shape, {-2: f(shape, -2)})
        if name == "out_proj":                    # mamba (di, d) row-parallel
            return _assemble(shape, {-2: M(shape, -2), -1: f(shape, -1)})
        if "vis_proj" in ctx and name == "w":     # (d_vis, d) then (d, d)
            return _assemble(shape, {-2: f(shape, -2), -1: M(shape, -1)})
        # norms / biases / conv / dt / A_log / D / small vectors: replicate
        return P()

    def params(self, params_struct: Any) -> Any:
        flat, tdef = jax.tree_util.tree_flatten_with_path(params_struct)
        specs = [self._ns(self._param_spec(_path_names(p), leaf.shape))
                 for p, leaf in flat]
        return jax.tree_util.tree_unflatten(tdef, specs)

    # ------------------------------------------------------------------
    # optimizer state (mirrors params under m/v; scalars replicated)
    # ------------------------------------------------------------------
    def opt_state(self, opt_struct: Any) -> Any:
        flat, tdef = jax.tree_util.tree_flatten_with_path(opt_struct)
        specs = []
        for p, leaf in flat:
            names = _path_names(p)
            if leaf.ndim == 0:
                specs.append(self._ns(P()))
                continue
            # strip the leading "m"/"v" key and apply the param rule
            inner = names[1:] if names and names[0] in ("m", "v") else names
            specs.append(self._ns(self._param_spec(inner, leaf.shape)))
        return jax.tree_util.tree_unflatten(tdef, specs)

    # ------------------------------------------------------------------
    # KV / SSM caches
    # ------------------------------------------------------------------
    def _cache_spec(self, path: list[str], shape: tuple, batch: int) -> P:
        name = path[-1] if path else ""
        asg: dict[int, Any] = {}
        if name in ("k", "v", "mem_k", "mem_v"):
            # trailing (B, W, kv, hd)
            if len(shape) < 4:
                return P()
            b_ok = _ok(shape, -4, self.data) and shape[-4] == batch
            if b_ok:
                asg[-4] = "data"
            if _ok(shape, -2, self.model):
                asg[-2] = "model"                  # kv heads
            elif _ok(shape, -3, self.model):
                asg[-3] = "model"                  # seq-parallel decode
            if not b_ok and _ok(shape, -3, self.data) and -3 not in asg:
                asg[-3] = "data"                   # long ctx, idle batch axis
            elif not b_ok and -3 in asg and asg[-3] == "model" \
                    and _ok(shape, -3, self.data * self.model):
                asg[-3] = ("data", "model")
            return _assemble(shape, asg)
        if name in ("k_scale", "v_scale"):
            # trailing (B, W, kv) — mirror the k/v rules minus head_dim
            if len(shape) < 3:
                return P()
            b_ok = _ok(shape, -3, self.data) and shape[-3] == batch
            if b_ok:
                asg[-3] = "data"
            if _ok(shape, -1, self.model):
                asg[-1] = "model"
            elif _ok(shape, -2, self.model):
                asg[-2] = "model"
            if not b_ok and _ok(shape, -2, self.data) and -2 not in asg:
                asg[-2] = "data"
            return _assemble(shape, asg)
        if name in ("ckv", "k_rope"):
            # trailing (B, S, r). Shard the SEQUENCE over "model" (and over
            # "data" too when batch is idle): the decode score einsum then
            # stays shard-local with a distributed softmax, instead of
            # GSPMD all-gathering the whole latent cache per layer (537 MB
            # ×L — the r-sharded layout's failure mode).
            if len(shape) < 3:
                return P()
            b_ok = _ok(shape, -3, self.data) and shape[-3] == batch
            if b_ok:
                asg[-3] = "data"
                if _ok(shape, -2, self.model):
                    asg[-2] = "model"
            elif _ok(shape, -2, self.data * self.model):
                asg[-2] = ("data", "model")        # long ctx, idle batch
            elif _ok(shape, -2, self.model):
                asg[-2] = "model"
            return _assemble(shape, asg)
        if name == "conv":
            # trailing (B, K-1, conv_dim)
            if len(shape) >= 3 and _ok(shape, -3, self.data) \
                    and shape[-3] == batch:
                asg[-3] = "data"
            if len(shape) >= 1 and _ok(shape, -1, self.model):
                asg[-1] = "model"
            return _assemble(shape, asg)
        if name == "state":
            # trailing (B, nh, hd, ds)
            if len(shape) >= 4 and _ok(shape, -4, self.data) \
                    and shape[-4] == batch:
                asg[-4] = "data"
            if len(shape) >= 3 and _ok(shape, -3, self.model):
                asg[-3] = "model"                  # SSD heads
            return _assemble(shape, asg)
        return P()

    def cache(self, cache_struct: Any, batch: int) -> Any:
        flat, tdef = jax.tree_util.tree_flatten_with_path(cache_struct)
        specs = [self._ns(self._cache_spec(_path_names(p), leaf.shape, batch))
                 for p, leaf in flat]
        return jax.tree_util.tree_unflatten(tdef, specs)

    # ------------------------------------------------------------------
    # batches
    # ------------------------------------------------------------------
    def _batch_spec(self, shape: tuple) -> P:
        if not shape or shape[0] % self.data_total != 0 \
                or shape[0] < self.data_total:
            return P()
        ax = self.batch_axes if len(self.batch_axes) > 1 else \
            self.batch_axes[0]
        return _assemble(shape, {-len(shape): ax})

    def batch(self, batch_struct: Any) -> Any:
        return jax.tree.map(
            lambda leaf: self._ns(self._batch_spec(leaf.shape)),
            batch_struct)

    def replicated(self, struct: Any) -> Any:
        return jax.tree.map(lambda _: self._ns(P()), struct)

    # ------------------------------------------------------------------
    # container placement (sub-mesh serving)
    # ------------------------------------------------------------------
    def container_placement(self, struct: Any) -> Any:
        """Placement for one container's params/caches on its sub-mesh:
        replicated across the slice. The container axis carries the
        parallelism (containers are full replicas — the paper's model);
        intra-container tensor parallelism (``params()``/``cache()`` on
        the same sub-mesh) is the pod-scale extension, but it changes
        matmul reduction order, so the bit-parity contract between n and
        the single-device baseline holds only for replicas."""
        return self.replicated(struct)


def tree_device_set(tree: Any) -> frozenset:
    """Union of the device sets of every committed leaf in ``tree`` —
    what the sub-mesh placement tests assert disjointness over."""
    out: set = set()
    for leaf in jax.tree.leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            out |= set(sharding.device_set)
    return frozenset(out)
