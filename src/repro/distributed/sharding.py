"""Logical-axis sharding rules with divisibility fallback.

Every tensor dim carries a *logical name* ("batch", "heads_flat", ...).
A rule maps a name to an ordered list of mesh-axis candidates; the
resolver picks, per tensor, the first candidate that (a) exists in the
mesh, (b) divides the dim size, (c) doesn't reuse a mesh axis already
assigned to another dim of the same tensor. Names are resolved in a
global priority order (not dim order) so e.g. KV-head sharding wins the
"model" axis before sequence sharding falls back to it.

This is what makes every (arch × shape × mesh) dry-run cell compile:
n_heads=14 on a 16-way model axis falls back to sharding the fused
``heads*head_dim`` dim; global_batch=1 falls back to sequence sharding;
anything else falls back to replication instead of a GSPMD error.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with the repo's default ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

# candidates: tuples of mesh axis names (joint sharding) tried in order;
# () means replicate.
DEFAULT_RULES: dict[str, list[tuple[str, ...]]] = {
    # data-parallel axes
    "batch":       [("pod", "data"), ("data",), ()],
    # tensor-parallel output dims
    "heads_flat":  [("model",), ()],
    "kv_flat":     [("model",), ()],
    "kv_heads":    [("model",), ()],
    "heads":       [("model",), ()],
    "mlp":         [("model",), ()],
    "vocab":       [("model",), ()],
    "experts":     [("model",), ()],
    "inner":       [("model",), ()],      # SSM/xLSTM expanded channels
    # FSDP: parameters' reduction dims shard over the data axis
    "embed":       [("data",), ()],
    "embed_pod":   [("pod", "data"), ("data",), ()],  # opt-in ZeRO over pods
    # sequence axes
    "kv_seq":      [("model",), ("data",), ()],
    "seq":         [()],
    # packed leading item axis of a grouped C step (core/grouping.py):
    # the stacked items are embarrassingly parallel, so they data-shard
    "items":       [("data",), ()],
    # never sharded
    "layers":      [()],
    "state":       [()],
    "lora":        [()],
    "conv":        [()],
    "gates":       [()],
    "stack":       [()],
    None:          [()],
}

# Serving rules: weights are TP-sharded only ("embed" replicates).
# FSDP (sharding the reduction dim over "data") amortizes over the many
# uses per step in training; in decode it would re-gather every weight
# every token — measured 11.3 GB/step of pure all-gather on
# mixtral-8x7b decode_32k (EXPERIMENTS.md §Perf, cell 2 iteration 1).
SERVE_RULES = None  # initialized below


# greedy assignment priority (earlier names grab mesh axes first)
PRIORITY = [
    "experts", "items", "batch", "heads_flat", "kv_flat", "heads",
    "kv_heads", "mlp", "vocab", "inner", "embed", "embed_pod", "kv_seq",
    "seq",
]


SERVE_RULES = dict(DEFAULT_RULES)
SERVE_RULES["embed"] = [()]
SERVE_RULES["embed_pod"] = [()]


def resolve_spec(names: tuple, shape: tuple, mesh: Mesh,
                 rules: dict | None = None) -> P:
    """Logical dim names + concrete shape → PartitionSpec."""
    rules = rules or DEFAULT_RULES
    assert len(names) == len(shape), (names, shape)
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    order = sorted(
        range(len(names)),
        key=lambda i: PRIORITY.index(names[i]) if names[i] in PRIORITY
        else len(PRIORITY))
    used: set[str] = set()
    entries: list = [None] * len(names)
    for i in order:
        name = names[i]
        for cand in rules.get(name, [()]):
            if not cand:
                entries[i] = None
                break
            if not all(a in mesh_sizes for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            prod = 1
            for a in cand:
                prod *= mesh_sizes[a]
            if shape[i] % prod != 0:
                continue
            entries[i] = cand if len(cand) > 1 else cand[0]
            used.update(cand)
            break
    return P(*entries)


# ----------------------------------------------------------------------
# Packed-item axis of the grouped C step (core/grouping.py). Unlike
# resolve_spec — which can only fall back to replication when a dim
# doesn't divide the mesh axis — an item stack may be *padded*: the items
# are independent (the scheme is vmapped over them), so extra zero items
# change nothing but the shard shapes.
# ----------------------------------------------------------------------
def items_partition(n_items: int, mesh: Mesh, rules: dict | None = None,
                    allow_pad: bool = True) -> tuple:
    """Resolve the ``"items"`` logical axis for a packed stack of
    ``n_items``.

    Returns ``(entry, pad)``: ``entry`` is the PartitionSpec entry for
    the leading axis (a mesh-axis name, a tuple of them, or ``None`` for
    replicate) and ``pad`` is how many zero items to append so the padded
    count divides the assigned mesh axes. With ``allow_pad=False`` only
    exact divisibility shards (used for per-task output specs, where the
    slice must keep the task's true item count).
    """
    rules = rules or DEFAULT_RULES
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for cand in rules.get("items", [()]):
        if not cand:
            return None, 0
        if not all(a in mesh_sizes for a in cand):
            continue
        prod = 1
        for a in cand:
            prod *= mesh_sizes[a]
        pad = (-n_items) % prod
        if pad and not allow_pad:
            continue
        return (cand if len(cand) > 1 else cand[0]), pad
    return None, 0


def stacked_sharding(mesh: Mesh, entry, ndim: int) -> NamedSharding:
    """NamedSharding that splits only the leading (item) axis."""
    return NamedSharding(mesh, P(entry, *([None] * (ndim - 1))))


def match_shardings(tree, like):
    """Re-lay ``tree``'s leaves onto the shardings of ``like``'s leaves.

    Used by the overlapped trainer when it swaps fresh Δ(Θ)/λ refs into
    the train state mid-L-step: the compiled train step was traced
    against the *old* refs' layouts, so the replacements must land on
    identical shardings or every subsequent microbatch pays a resharding
    (or worse, a recompile). ``jax.device_put`` with a sharding is
    async — the swap itself never stalls the pipeline. Leaves whose
    sharding already matches (the common case: the C step's per-task
    output constraints) pass through untouched.
    """
    def put(x, y):
        want = getattr(y, "sharding", None)
        if want is None or getattr(x, "sharding", None) == want:
            return x
        return jax.device_put(x, want)

    return jax.tree_util.tree_map(put, tree, like)


# ----------------------------------------------------------------------
# Active-mesh context so model code can constrain activations without
# threading mesh/rules through every call.
# ----------------------------------------------------------------------
_CTX = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    prev = getattr(_CTX, "state", None)
    _CTX.state = (mesh, rules or DEFAULT_RULES) if mesh is not None else None
    try:
        yield
    finally:
        _CTX.state = prev


def active_mesh() -> Mesh | None:
    st = getattr(_CTX, "state", None)
    return st[0] if st else None


def constrain(x, names: tuple):
    """with_sharding_constraint against the active mesh (no-op if none)."""
    st = getattr(_CTX, "state", None)
    if st is None:
        return x
    mesh, rules = st
    spec = resolve_spec(names, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that carry the batch (everything except 'model')."""
    return tuple(n for n in mesh.axis_names if n != "model")


def tree_shardings(axes_tree, shapes_tree, mesh: Mesh,
                   rules: dict | None = None):
    """Pytree of logical-name tuples + matching shapes → NamedShardings."""
    return jax.tree_util.tree_map(
        lambda names, shape: NamedSharding(
            mesh, resolve_spec(tuple(names), tuple(shape), mesh, rules)),
        axes_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, (tuple, list)) and
        all(isinstance(e, (str, type(None))) for e in x))
