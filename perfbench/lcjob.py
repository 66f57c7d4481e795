"""Cells of kind ``lc``: the LC compression job through ``LCTrainer``.

Set-up builds one trainer, initialises its state from the seed (weights,
optimizer, direct compression), and runs the first LC iteration through
the trainer's own loop: its train steps compile there, and so do the C
step, the multiplier step and the monitors. The first ``ref_steps``
optimizer steps of that iteration are read for the comparison. The
window then runs ``n`` more whole LC iterations through the same loop
(``LCTrainer._run_serial``), ``n = round(seconds / iteration_s)`` with
``iteration_s`` from the traffic file, so every run does the same work.

``lc_tokens_per_s`` is every L-step token of the window over the wall
time of the whole window, C steps, multiplier steps and monitors
included.

``correct`` compares the program with a plain float32 reference
(``refs.py`` and the configuration's ``.ref.py``) that imports nothing of
the program. The L step: the reference draws the same weights from the
seed, compresses them with its own top-kappa and k-means, and takes the
same AdamW steps on the same token batches:
  * ``loss_gap``: the largest relative gap of a step's loss;
  * ``grad_gap``: the first gradient as the optimizer got it (its first
    moment after one step, over 1 - b1), per leaf, the gap of the norms
    over the larger of the reference leaf's norm and the median leaf's;
  * ``change_gap``: the parameters' change over the ``ref_steps`` steps,
    per leaf as for ``grad_gap``, leaving out leaves whose reference
    gradient is under a thousandth of the median leaf's (Adam moves them
    by round-off alone);
  * ``aux_gap`` (configurations with ``moe``): the largest relative gap
    of a step's router balance term, unweighted and summed over the MoE
    layers: the program's ``metrics["aux"]`` against the reference's at
    its own parameters and the same batch. A broken router is caught here
    on its own, where its share of the loss is too small for
    ``loss_gap``.
The reference's loss is the mean next-token NLL of its ``logits_row``
over the batch, plus, where the ``.ref.py`` defines it, the batch-level
``aux_loss(p, tokens, c, mm)`` (the weighted term the model adds; its
gradient is not divided by the token count), plus the LC penalty.
The C step: the input of the window's last C step, x = w - lambda / mu
(the weights, multipliers and mu that step was given), is kept, and the
reference compresses it again (top-kappa by a full sort, k-means by
Lloyd from quantiles) and is compared with Delta(Theta) as the program
returned it, per task item (a k-means task's item is its whole leaf, or
with ``stack_ndim`` n one index on the leaf's n leading axes, as the
program's ``AsStacked`` view splits it; top-kappa's is all its leaves):
  * ``cstep_gap``: the worst item's distortion ||x - Delta||^2 over the
    reference's, less 1 (below 0 where the program found a better
    codebook than the reference);
  * ``cstep_excess``: the most values an item's Delta(Theta) holds beyond
    what its scheme allows (distinct values beyond k for k-means,
    non-zeros beyond kappa for top-kappa), compared exactly.
"""
from __future__ import annotations

import re

import time

import jax
import jax.numpy as jnp
import numpy as np

import flops
import harness
import refs

FP8 = jnp.float8_e4m3fn


# ----------------------------------------------------------------------
# inputs
# ----------------------------------------------------------------------
class Feed:
    """Token batches drawn from the seed; ``batch_at(step)`` is a pure
    function of the step. Rows differ in how much they repeat: in row r
    of B each token repeats the one before it with probability r / B,
    and is drawn uniformly from the vocabulary otherwise, so rows run
    from noise to long runs of one token, as documents differ in how
    predictable they are."""

    def __init__(self, seed: int, vocab: int, batch: int, seq: int):
        self.key = harness.seed_key(seed, 1)

        @jax.jit
        def gen(key):
            kt, kr = jax.random.split(key)
            fresh = jax.random.randint(kt, (batch, seq + 1), 0, vocab,
                                       jnp.int32)
            p = jnp.arange(batch, dtype=jnp.float32)[:, None] / batch
            new = jax.random.uniform(kr, (batch, seq + 1)) >= p
            new = new.at[:, 0].set(True)
            pos = jnp.where(new, jnp.arange(seq + 1)[None, :], 0)
            src = jax.lax.cummax(pos, axis=1)
            t = jnp.take_along_axis(fresh, src, axis=1)
            return {"inputs": t[:, :-1], "labels": t[:, 1:]}
        self._gen = gen

    def batch_at(self, step: int) -> dict:
        return self._gen(jax.random.fold_in(self.key, int(step)))


def n_iterations(traffic: dict, seconds: float) -> int:
    return max(1, int(round(seconds / traffic["iteration_s"])))


def tokens_per_iteration(tr: dict) -> int:
    return tr["steps_per_l"] * tr["batch"] * tr["seq_len"]


# ----------------------------------------------------------------------
# the program
# ----------------------------------------------------------------------
TASK_KEYS = {"kmeans": {"k", "iters", "stack_ndim"},
             "topk": {"kappa_divisor"}}


def resolve_tasks(tr: dict, leaves: dict) -> list[dict]:
    """The traffic's compression tasks resolved against ``leaves``
    (parameter path to array or shape): one entry per task as the program
    gets it. A ``per_leaf`` task is one task per matching leaf; otherwise
    top-kappa keeps ``1 / kappa_divisor`` of all matching weights as one
    vector. A k-means task is ``per_leaf``; its ``stack_ndim`` leading
    axes of each leaf (0 by default) index separate items, each with its
    own codebook. A task the program cannot be given is an error."""
    out = []
    for i, t in enumerate(tr["tasks"]):
        if t["scheme"] not in TASK_KEYS:
            raise ValueError(f"task {i}: unknown scheme {t['scheme']!r}")
        extra = set(t) - {"scheme", "pattern", "per_leaf"} - TASK_KEYS[t["scheme"]]
        if extra:
            raise ValueError(f"task {i}: keys {sorted(extra)} are not "
                             f"keys of a {t['scheme']} task")
        if t["scheme"] == "kmeans" and not t.get("per_leaf"):
            raise ValueError(f"task {i}: a k-means task is per_leaf; its "
                             f"items within a leaf are set by stack_ndim")
        rx = re.compile(t["pattern"])
        match = sorted(p for p in leaves if rx.search(p))
        if not match:
            raise ValueError(f"task {i}: {t['pattern']!r} matches no leaf")
        n = t.get("stack_ndim", 0)
        for p in match:
            if n < 0 or (n and n >= len(leaves[p].shape)):
                raise ValueError(f"task {i}: stack_ndim {n} leaves no item "
                                 f"axis in {p} {tuple(leaves[p].shape)}")
        if t.get("per_leaf"):
            out += [dict(t, name=p, paths=[p]) for p in match]
        else:
            out.append(dict(t, name=f"{t['scheme']}-{i}", paths=match))
    return out


def program_tasks(tr: dict, cfg) -> list:
    from repro.core import AsStacked, AsVector, CompressionTask
    from repro.core.schemes import AdaptiveQuantization, ConstraintL0Pruning
    from repro.core.tasks import flatten_params
    from repro.models.transformer import init_params

    shapes = flatten_params(jax.eval_shape(lambda k: init_params(k, cfg),
                                           jax.random.PRNGKey(0)))
    out = []
    for t in resolve_tasks(tr, shapes):
        pattern = "^(" + "|".join(re.escape(p) for p in t["paths"]) + ")$"
        if t["scheme"] == "kmeans":
            n = t.get("stack_ndim", 0)
            view = AsStacked("vector", stack_ndim=n) if n else AsVector()
            scheme = AdaptiveQuantization(k=t["k"], iters=t["iters"])
        else:
            total = sum(shapes[p].size for p in t["paths"])
            view = AsVector()
            scheme = ConstraintL0Pruning(
                kappa=max(1, total // t["kappa_divisor"]))
        out.append(CompressionTask(t["name"], pattern, view, scheme))
    return out


def model_config(c: dict):
    """The program's ``ModelConfig`` of configuration file ``c``; the
    feed draws token ids, so a model fed embeddings is refused. With
    ``moe`` the ``.ref.py`` must define ``aux_loss``: at a non-zero
    ``router_aux_weight`` the program adds the balance term to its loss,
    and at any weight ``aux_gap`` reads it."""
    cfg = harness.model_config(c)
    if cfg.input_mode != "tokens":
        raise ValueError(f"configuration {cfg.name!r}: input_mode "
                         f"{cfg.input_mode!r}; the LC job feeds token ids")
    if cfg.moe is not None:
        if "reference" not in c:
            raise ValueError(f"configuration {cfg.name!r}: moe needs a "
                             f"reference (.ref.py) that defines aux_loss")
        if not hasattr(reference(c), "aux_loss"):
            raise ValueError(
                f"configuration {cfg.name!r}: {c['reference']} defines no "
                f"aux_loss; router_aux_weight is "
                f"{cfg.moe.router_aux_weight}, and aux_gap reads the "
                f"balance term at any weight")
    return cfg


def reference(c: dict):
    """The plain reference module named by configuration file ``c``."""
    return refs.model_reference(c["reference"][:-len(".ref.py")])


def cell_config(cell: dict):
    """``model_config`` of the cell's configuration; a cell whose
    configuration has ``moe`` must give ``aux_gap`` in its limits."""
    cfg = model_config(cell["config_file"])
    if cfg.moe is not None and "aux_gap" not in cell["limits"]:
        raise ValueError(f"perfbench/limits/{cell['name']}.json gives no "
                         f"aux_gap; the configuration has moe")
    return cfg


def build_trainer(cell: dict, seed: int, n_lc: int, fault: str | None = None):
    from repro.core import LCAlgorithm, exponential_mu_schedule
    from repro.launch.mesh import make_debug_mesh
    from repro.optim import AdamW
    from repro.runtime import LCTrainer, TrainerConfig

    tr = cell["traffic_file"]
    cfg = cell_config(cell)
    if fault in CONFIG_FAULTS:
        cfg = CONFIG_FAULTS[fault](cfg)
    lc = LCAlgorithm(program_tasks(tr, cfg),
                     exponential_mu_schedule(tr["mu0"], tr["mu_a"], n_lc))
    feed = Feed(seed, cfg.vocab_size, tr["batch"], tr["seq_len"])
    return LCTrainer(cfg, lc, feed, mesh=make_debug_mesh(),
                     tcfg=TrainerConfig(steps_per_l=tr["steps_per_l"],
                                        lr=tr["lr"],
                                        clip_norm=tr["clip_norm"]),
                     optimizer=AdamW(**tr["adam"]))


@jax.jit
def _leaf_norms(tree):
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in refs.flatten(tree).items()}


def host_norms(tree) -> dict:
    return {p: float(v) for p, v in jax.device_get(_leaf_norms(tree)).items()}


def change_norms(new: dict, old: dict) -> dict:
    return {p: float(np.linalg.norm((new[p].astype(np.float64)
                                     - old[p].astype(np.float64)).ravel()))
            for p in old}


def capture_readings(trainer, n_steps: int, b1: float) -> dict:
    """Wrap the trainer's step so that its first ``n_steps`` steps are
    read: each step's loss (and, with ``moe``, its balance term), the
    first gradient (from Adam's first moment) and the parameters' change.
    Returns the dict the readings fill."""
    out = {"loss": []}
    if trainer.cfg.moe is not None:
        out["aux"] = []
    step_fn = trainer._one_step

    def one_step(state, step):
        if step == 0:
            out["p0"] = refs.flatten(harness.tree_to_host(state["params"]))
        new, metrics = step_fn(state, step)
        if step < n_steps:
            out["loss"].append(float(metrics["loss"]))
            if "aux" in out:
                out["aux"].append(float(metrics["aux"]))
        if step == 0:
            out["grad"] = {p: v / (1.0 - b1)
                           for p, v in host_norms(new["opt"]["m"]).items()}
        if step == n_steps - 1:
            p = refs.flatten(harness.tree_to_host(new["params"]))
            out["change"] = change_norms(p, out.pop("p0"))
        return new, metrics
    trainer._one_step = one_step
    return out


FAULTS = {
    # the step hands back the state it was given
    "state_unchanged": lambda step: lambda st, b: (st, step(st, b)[1]),
    # half of the batch left out, the mean taken over the rest
    "half_batch": lambda step: lambda st, b: step(st, jax.tree_util.tree_map(
        lambda x: x[:x.shape[0] // 2], b)),
    # the labels altered where the step takes them: each position's own
    # token instead of the next one
    "labels_altered": lambda step: lambda st, b: step(
        st, {"inputs": b["inputs"], "labels": b["inputs"]}),
    # the router's choice altered: its k least likely experts
    "router_flipped": lambda step: lambda st, b: _bottom_k(step, st, b),
}


def _bottom_k(step, state, batch):
    """``step`` with ``jax.lax.top_k`` taking the k smallest values, for
    as long as the call lasts: the first call traces the step."""
    top_k = jax.lax.top_k

    def bottom_k(x, k):
        v, i = top_k(-x, k)
        return -v, i
    jax.lax.top_k = bottom_k
    try:
        return step(state, batch)
    finally:
        jax.lax.top_k = top_k


def _aux_dropped(cfg):
    import dataclasses
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, router_aux_weight=0.0))


# faults planted in the configuration the program is built from: the
# balance term left out of the program's loss
CONFIG_FAULTS = {"aux_dropped": _aux_dropped}


def _deltas_altered(step):
    """Delta(Theta) altered where the C step produces it: the input
    itself, not compressed."""
    def c_step(params, lc):
        x = _shift(params, lc)
        out = step(params, lc)
        return dict(out, tasks={n: dict(ts, a={p: x[p] for p in ts["a"]})
                                for n, ts in out["tasks"].items()})
    return c_step


CSTEP_FAULTS = {
    # the C step hands back the LC state it was given
    "cstep_unchanged": lambda step: lambda params, lc: lc,
    "cstep_altered": _deltas_altered,
}


def plant(trainer, fault: str | None):
    """Break the program's train step with one of ``FAULTS``, or its C
    step with one of ``CSTEP_FAULTS`` (``CONFIG_FAULTS`` are planted by
    ``build_trainer``)."""
    if fault in FAULTS:
        trainer._train_step = FAULTS[fault](trainer._train_step)
    elif fault is not None and fault not in CONFIG_FAULTS:
        trainer.lc.c_step = CSTEP_FAULTS[fault](trainer.lc.c_step)


def program_readings(cell: dict, seed: int, fault: str | None = None) -> dict:
    """The readings of the program's first ``ref_steps`` steps, taken
    through the trainer's own step and feed, with no window."""
    tr = cell["traffic_file"]
    trainer = build_trainer(cell, seed, 1, fault)
    plant(trainer, fault)
    readings = capture_readings(trainer, tr["ref_steps"], tr["adam"]["b1"])
    state = trainer.init_state(harness.seed_key(seed))
    for s in range(tr["ref_steps"]):
        state, _ = trainer._one_step(state, s)
    jax.block_until_ready(state)
    del state, trainer
    harness.free_device()
    return readings


# ----------------------------------------------------------------------
# the reference
# ----------------------------------------------------------------------
def item_rows(t: dict, w):
    """Leaf ``w`` of k-means task ``t`` as one row per item: its
    ``stack_ndim`` leading axes index the items."""
    n = t.get("stack_ndim", 0)
    return w.reshape(int(np.prod(w.shape[:n])), -1)


def compress(t: dict, leaves: list, iters: int | None = None,
             dtype=None) -> list:
    """The reference's Delta(Theta) of task ``t`` over ``leaves`` (its
    paths' arrays, in order): top-kappa over the concatenated leaves, or
    k-means per item of its leaf (``item_rows``). ``iters`` Lloyd steps
    (default: the task's); ``dtype`` computes the k-means in a lower
    precision (the control)."""
    iters = t["iters"] if iters is None and "iters" in t else iters
    if t["scheme"] == "topk":
        vec = jnp.concatenate([w.ravel() for w in leaves])
        kappa = max(1, vec.size // t["kappa_divisor"])
        kept = jax.jit(refs.topk_keep, static_argnums=1)(vec, kappa)
        out, off = [], 0
        for w in leaves:
            out.append(kept[off:off + w.size].reshape(w.shape))
            off += w.size
        return out
    km = jax.jit(refs.kmeans, static_argnums=(1, 2, 3, 4))
    (w,) = leaves
    if not t.get("stack_ndim"):
        return [km(w, t["k"], iters, 1 << 20, dtype).reshape(w.shape)]
    return [jnp.stack([km(r, t["k"], iters, 1 << 20, dtype)
                       for r in item_rows(t, w)]).reshape(w.shape)]


def direct_compression(tasks: list[dict], flat: dict) -> dict:
    """Delta(Theta) of the first C step from the weights themselves."""
    a = {}
    for t in tasks:
        a.update(zip(t["paths"], compress(t, [flat[p] for p in t["paths"]])))
    return a


def reference_readings(cell: dict, seed: int, mm: refs.MatMul) -> dict:
    """The reference's loss per step (and, with ``aux_loss``, its
    unweighted balance term), first clipped gradient and parameter change
    over ``ref_steps`` AdamW steps."""
    c, tr = cell["config_file"], cell["traffic_file"]
    cell_config(cell)
    ref = reference(c)
    aux_loss = getattr(ref, "aux_loss", None)
    ad = tr["adam"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.init_params(k, c))(harness.seed_key(seed))
        flat = refs.flatten(params)
        a = direct_compression(resolve_tasks(tr, flat), flat)
        mu = tr["mu0"]
        feed = Feed(seed, c["vocab_size"], tr["batch"], tr["seq_len"])

        def batch_nll(p, toks, labels):
            return jnp.sum(jax.vmap(
                lambda t, l: refs.row_nll(ref.logits_row(p, t, c, mm), l))(
                    toks, labels))
        batch_vg = jax.jit(jax.value_and_grad(batch_nll))
        if aux_loss is not None:
            # the weighted term the model adds, with the file's weight; at
            # weight 0 the unweighted one is read at weight 1
            w = c["moe"]["router_aux_weight"]
            aux_vg = jax.jit(jax.value_and_grad(
                lambda p, toks: aux_loss(p, toks, c, mm)))
            c1 = dict(c, moe=dict(c["moe"], router_aux_weight=1.0))
            aux_at_one = jax.jit(lambda p, toks: aux_loss(p, toks, c1, mm))

        @jax.jit
        def finish(p, g_sum, nll_sum, n_tok, a, aux=None, aux_g=None):
            fp = refs.flatten(p)
            pen = sum(0.5 * mu * jnp.sum(jnp.square(fp[q] - a[q])) for q in a)
            fg = refs.flatten(jax.tree_util.tree_map(lambda g: g / n_tok,
                                                     g_sum))
            loss = nll_sum / n_tok
            if aux is not None:
                fa = refs.flatten(aux_g)
                fg = {q: fg[q] + fa[q] for q in fg}
                loss = loss + aux
            for q in a:
                fg[q] = fg[q] + mu * (fp[q] - a[q])
            return loss + pen, refs.unflatten(fg)

        @jax.jit
        def update(p, g, m, v, t):
            g = refs.clip_global(g, tr["clip_norm"])
            p, m, v = refs.adamw(p, g, m, v, t, tr["lr"], ad["b1"], ad["b2"],
                                 ad["eps"], ad["weight_decay"])
            return p, m, v, g

        p0 = refs.flatten(harness.tree_to_host(params))
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        out = {"loss": []}
        if aux_loss is not None:
            out["aux"] = []
        for s in range(tr["ref_steps"]):
            batch = feed.batch_at(s)
            nll_sum, g_sum = batch_vg(params, batch["inputs"], batch["labels"])
            aux = aux_g = None
            if aux_loss is not None:
                aux, aux_g = aux_vg(params, batch["inputs"])
                out["aux"].append(float(aux) / w if w else
                                  float(aux_at_one(params, batch["inputs"])))
            loss, grads = finish(params, g_sum, nll_sum,
                                 float(tr["batch"] * tr["seq_len"]), a,
                                 aux, aux_g)
            out["loss"].append(float(loss))
            params, m, v, clipped = update(params, grads, m, v,
                                           jnp.float32(s + 1))
            if s == 0:
                out["grad"] = host_norms(clipped)
        out["change"] = change_norms(
            refs.flatten(harness.tree_to_host(params)), p0)
    return out


# ----------------------------------------------------------------------
# the C step's input and output
# ----------------------------------------------------------------------
@jax.jit
def _shift(params, lc):
    """x = w - lambda / mu of every compressed leaf: the C step's input."""
    flat = refs.flatten(params)
    return {p: flat[p].astype(jnp.float32) - ts["lam"][p] / lc["mu"]
            for ts in lc["tasks"].values() for p in ts["lam"]}


@jax.jit
def _copy_a(lc):
    return {p: ts["a"][p] * 1.0
            for ts in lc["tasks"].values() for p in ts["a"]}


class CStepCapture:
    """Stands in for ``trainer.lc.c_step``; while ``armed`` it keeps the
    input x of each C step (and, with ``keep_prev``, the Delta(Theta) the
    step starts from) before the step, which donates its LC state, runs."""

    def __init__(self, trainer, keep_prev: bool = False):
        self.fn = trainer.lc.c_step
        self.keep_prev = keep_prev
        self.armed = False
        self.x = self.prev = None
        trainer.lc.c_step = self

    def __call__(self, params, lc):
        if self.armed:
            self.x = _shift(params, lc)
            if self.keep_prev:
                self.prev = _copy_a(lc)
        return self.fn(params, lc)


def to_host(tree: dict) -> dict:
    return {p: np.asarray(v) for p, v in jax.device_get(tree).items()}


def lc_deltas(lc_state) -> dict:
    return {p: a for ts in lc_state["tasks"].values()
            for p, a in ts["a"].items()}


# ----------------------------------------------------------------------
# comparison
# ----------------------------------------------------------------------
def gaps(got: dict, ref: dict) -> dict:
    """The L step's numbers (see the module docstring)."""
    loss = max(abs(g - r) / abs(r) for g, r in zip(got["loss"], ref["loss"]))
    gr = ref["grad"]
    med_g = float(np.median(list(gr.values())))
    grad = {p: abs(got["grad"][p] - gr[p]) / max(gr[p], med_g) for p in gr}
    moved = [p for p in gr if gr[p] >= 1e-3 * med_g]
    cr = ref["change"]
    med_c = float(np.median([cr[p] for p in moved]))
    change = {p: abs(got["change"][p] - cr[p]) / max(cr[p], med_c)
              for p in moved}
    top = lambda d: [[p, d[p]] for p in sorted(d, key=d.get)[::-1][:4]]
    out = {"loss_gap": loss, "grad_gap": max(grad.values()),
           "change_gap": max(change.values()),
           "grad_gap_median": float(np.median(list(grad.values()))),
           "change_gap_median": float(np.median(list(change.values()))),
           "worst": {"grad": top(grad), "change": top(change)},
           "step_loss_gaps": [abs(g - r) / abs(r) for g, r in
                              zip(got["loss"], ref["loss"])],
           "left_out": sorted(set(gr) - set(moved))}
    if "aux" in ref:
        out["aux_gap"] = max(abs(g - r) / abs(r)
                             for g, r in zip(got["aux"], ref["aux"]))
    return out


def items(t: dict, leaves: list) -> list:
    """The task's items as flat vectors: the concatenated leaves for
    top-kappa, each row of ``item_rows`` for k-means."""
    if t["scheme"] == "topk":
        return [jnp.concatenate([jnp.ravel(w) for w in leaves])]
    (w,) = leaves
    return list(item_rows(t, w))


def item_labels(t: dict, leaves: list) -> list[str]:
    """``<task>[i,j]`` of each item, by its index on the leaf's
    ``stack_ndim`` leading axes; ``<task>[0]`` for a whole leaf."""
    n = t.get("stack_ndim", 0)
    idx = np.ndindex(leaves[0].shape[:n]) if n else [(0,)]
    return [f"{t['name']}[{','.join(map(str, i))}]" for i in idx]


@jax.jit
def _distortion(x, d):
    return jnp.sum(jnp.square(x - d))


@jax.jit
def _distinct(d):
    s = jnp.sort(d)
    return 1 + jnp.sum(s[1:] != s[:-1])


def allowed(t: dict, n: int) -> int:
    return t["k"] if t["scheme"] == "kmeans" else max(1, n // t["kappa_divisor"])


def used(t: dict, d) -> int:
    return int(_distinct(d) if t["scheme"] == "kmeans"
               else jnp.count_nonzero(d))


def cstep_readings(tr: dict, x: dict, got: dict, prev: dict | None = None) -> dict:
    """The C step's numbers (see the module docstring) for Delta(Theta)
    ``got`` of input ``x``. With ``prev`` (calibration) also the gap of
    the control (the reference in float8 in the program's place) and of
    two faults: the step that hands back the Delta(Theta) it started from
    (``unchanged``) and one Lloyd step in place of the configured
    (``short``)."""
    ref_iters = tr["ref_kmeans_iters"]
    out = {"cstep_gap": -np.inf, "cstep_excess": -np.inf, "cstep_items": []}
    runs = {}
    if prev is not None:
        runs = {"control": lambda t, xs: compress(t, xs, ref_iters, FP8),
                "short": lambda t, xs: compress(t, xs, 1),
                "unchanged": lambda t, xs: [jnp.asarray(prev[p])
                                            for p in t["paths"]]}
        out.update({f"cstep_gap_{r}": -np.inf for r in runs})
    for t in resolve_tasks(tr, x):
        xs = [jnp.asarray(x[p]) for p in t["paths"]]
        x_it = items(t, xs)
        d_ref = [float(_distortion(a, b)) for a, b in
                 zip(x_it, items(t, compress(t, xs, ref_iters)))]
        g_it = items(t, [jnp.asarray(got[p]) for p in t["paths"]])
        for j, (a, b, label) in enumerate(zip(x_it, g_it,
                                              item_labels(t, xs))):
            gap = float(_distortion(a, b)) / d_ref[j] - 1.0
            excess = used(t, b) - allowed(t, a.size)
            out["cstep_items"].append([label, gap, excess])
            out["cstep_gap"] = max(out["cstep_gap"], gap)
            out["cstep_excess"] = max(out["cstep_excess"], excess)
        for r, fn in runs.items():
            for a, b, dr in zip(x_it, items(t, fn(t, xs)), d_ref):
                out[f"cstep_gap_{r}"] = max(out[f"cstep_gap_{r}"],
                                            float(_distortion(a, b)) / dr - 1)
        del xs, x_it, g_it
    return out


NUMBERS = ("loss_gap", "grad_gap", "change_gap", "aux_gap", "cstep_gap",
           "cstep_excess")


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    checks = {k: harness.checks_entry(numbers[k], limits[k])
              for k in NUMBERS if k in limits and k in numbers}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
SPANS = {"lc": [("c_step", "c_step"), ("multiplier_step", "multiplier_step"),
                ("shifted_distortion", "monitor.shifted_distortion"),
                ("distortion", "monitor.distortion"),
                ("penalty", "monitor.penalty"),
                ("compression_ratio", "monitor.compression_ratio")],
         "trainer": [("_one_step", "l_step.train_step")]}


def run(cell: dict, *, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict, fault: str | None = None, calibrate: bool = False) -> dict:
    tr = cell["traffic_file"]
    n = n_iterations(tr, seconds)
    # a traced run keeps its programs, to name the trace's ops by scope
    programs = harness.Programs() if trace else None
    trainer = build_trainer(cell, seed, 1 + n, fault)
    plant(trainer, fault)
    sched = trainer.lc.mu_schedule
    readings = capture_readings(trainer, tr["ref_steps"], tr["adam"]["b1"])
    capture = CStepCapture(trainer, keep_prev=calibrate)

    # set-up: initial state and the first LC iteration, which compiles
    # (the capture of the C step's input too)
    state = trainer.init_state(harness.seed_key(seed))
    capture.armed = True
    state, _ = trainer._run_serial(state, sched[:1], 0)
    capture.armed = False
    capture.x = capture.prev = None
    del trainer._one_step
    jax.block_until_ready(state)
    counter = harness.CompileCounter()
    prof = None
    if trace:
        for attr, span in SPANS["lc"]:
            harness.wrap(trainer.lc, attr, span)
        for attr, span in SPANS["trainer"]:
            harness.wrap(trainer, attr, span)
        prof = harness.Profiler()

    # window: n whole LC iterations through the trainer's loop; the input
    # of the last C step is kept. A trace covers the last iteration: the
    # profiler's buffers take device memory, and after it nothing else
    # runs on the device.
    step0 = tr["steps_per_l"]
    counter.active = True
    t0 = time.perf_counter()
    if n > 1:
        state, lc_state = trainer._run_serial(state, sched[1:n], step0)
    capture.armed = True
    if trace:
        prof.start()
    state, lc_state = trainer._run_serial(
        state, sched[n:1 + n], step0 + (n - 1) * tr["steps_per_l"])
    jax.block_until_ready((state, lc_state))
    if trace:
        prof.stop()
    t1 = time.perf_counter()
    counter.active = False
    setup_s = t0 - t_start
    window_s = t1 - t0
    history = trainer.history[1:]
    violations = [r["c_step_violations"] for r in history if r["c_step_violations"]]
    peak = harness.memory_peak_bytes()
    tokens = n * tokens_per_iteration(tr)

    metrics, breakdown, dev = {}, None, dict(device)
    dev["memory_peak_bytes"] = peak
    if trace:
        tr_ = prof.read()
        tr_.add_hlo(programs.texts(tr_.programs()))
        programs.close()
        dev["busy_s"] = tr_.busy_s()
        dev["window_s"] = tr_.window_s
        breakdown = tr_.breakdown()
        ctx = {"cell": cell, "trace": tr_, "history": history,
               "peaks": harness.peaks(device["kind"]),
               "tokens_traced": tokens_per_iteration(tr),
               "train_flops_per_token": flops.train_flops_per_token(
                   cell["config_file"], tr["seq_len"]),
               "compressed_weights": compressed_weights(trainer, state)}
        metrics = harness.per_layer(cell, ctx)
    else:
        e2e = {"lc_tokens_per_s": tokens / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    # the comparison, once the program's state is gone
    x, got = to_host(capture.x), to_host(lc_deltas(lc_state))
    prev = to_host(capture.prev) if calibrate else None
    capture.x = capture.prev = None
    del state, lc_state, trainer, capture
    harness.free_device()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, refs.MatMul())
    harness.free_device()
    numbers = dict(gaps(readings, ref), **cstep_readings(tr, x, got, prev))
    t_ref = time.perf_counter() - t_ref
    del x, got, prev
    harness.free_device()
    ok, checks = judge(numbers, cell["limits"])
    ok = ok and not violations
    result = {"correct": bool(ok),
              "attempted": n * tr["steps_per_l"],
              "failed": 0 if not violations else len(violations),
              "metrics": metrics, "device": dev,
              "window_compiles": counter.count,
              "lc_iterations": n, "window_wall_s": window_s,
              "reference_s": t_ref,
              "worst": {"cstep": sorted(numbers["cstep_items"],
                                        key=lambda r: -r[1])[:3],
                        **numbers["worst"]}}
    if calibrate:
        result["numbers"] = {k: v for k, v in numbers.items()
                             if k not in ("worst", "cstep_items")}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def compressed_weights(trainer, state) -> int:
    from repro.core.tasks import get_path
    return int(sum(get_path(state["params"], p).size
                   for t in trainer.lc.tasks for p in t.paths))


def calibrate(cell: dict, seed: int, runs: list[str]) -> list[dict]:
    """The L step's numbers for one seed, for each of ``runs``:
    "program", a fault's name (planted in the program), or "control"
    (the reference in float8 in the program's place). The float32
    reference is computed once for all of them."""
    got = {}
    for r in runs:
        if r == "control":
            got[r] = reference_readings(cell, seed, refs.MatMul(FP8))
        else:
            got[r] = program_readings(cell, seed,
                                      None if r == "program" else r)
        harness.free_device()
    ref = reference_readings(cell, seed, refs.MatMul())
    harness.free_device()
    out = []
    for r in runs:
        g = gaps(got[r], ref)
        out.append(dict(g, run=r, loss=got[r]["loss"], ref_loss=ref["loss"]))
    return out
