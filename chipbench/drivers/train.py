"""Training cells: ``Accelerator.prepare`` + ``make_train_step``, one fresh
seeded batch a step.

Set-up builds ONE object, the compiled step with its state, drives it from the
seed through its first ``check_steps`` steps (the warm-up, through the window's
own call and feed) and hands that same object to the window.  The check
replays those steps in the plain reference (``families/<family>.py``) once the
window has closed and the program's state is freed.

Traffic parameters: ``batch``, ``seq_len``, ``check_steps``, ``trace_seconds``.
Configuration: ``train`` (``mixed_precision``, ``optimizer``) and ``program``
(options of the program's model config).  Anything else is the program's default.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

SPANS = ("next_batch", "train_step", "wait_step")


class Driver:
    def __init__(self, ctx: dict):
        self.ctx = ctx
        self.cfg, self.traffic, self.family = ctx["cfg"], ctx["traffic"], ctx["family"]
        self.batch, self.seq = int(self.traffic["batch"]), int(self.traffic["seq_len"])
        self.rng = np.random.default_rng([int(ctx["seed"]), 7])
        self.fed = []  # the first batches, for the reference to replay

    # -- feed -----------------------------------------------------------------

    def next_batch(self):
        import jax

        ids = self.rng.integers(0, self.cfg["vocab_size"], (self.batch, self.seq), dtype=np.int32)
        if len(self.fed) < int(self.traffic["check_steps"]):
            self.fed.append(ids)
        return {"input_ids": jax.device_put(ids, self.sharding)}

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import optax

        from accelerate_tpu import Accelerator, JaxModel
        from accelerate_tpu.models import llama
        from accelerate_tpu.parallel.sharding import data_sharding

        fam, cfg = self.family, self.cfg
        train = cfg["train"]
        pcfg = fam.program_config(cfg)
        self.acc = Accelerator(mixed_precision=train["mixed_precision"])
        self.ctx["mark"]("accelerator")
        self.sharding = data_sharding(self.acc.mesh)

        def apply_fn(params, input_ids, attention_mask=None):
            batch = {"input_ids": input_ids, "attention_mask": attention_mask}
            return {"loss": llama.loss_fn(params, batch, pcfg)}

        opt = dict(train["optimizer"])
        if opt.pop("name") != "adamw":
            raise ValueError("drivers/train.py knows optax.adamw only")
        self.opt = opt
        params = fam.seeded_params(cfg, self.ctx["seed"])
        self.ctx["mark"]("weights_enqueued")
        self.model, self.optimizer = self.acc.prepare(
            JaxModel(apply_fn, params, partition_rules=llama.PARTITION_RULES), optax.adamw(**opt)
        )
        del params
        self.ctx["mark"]("model_prepared")
        self.step = self.acc.make_train_step(self.model, self.optimizer)
        self.ctx["mark"]("prepared")

        # The first steps: warm-up and the program's side of the check.
        n = int(self.traffic["check_steps"])
        self.losses, self.grad_sq = [], None
        for i in range(n):
            self.losses.append(self.step(self.next_batch()))
            if i == 0:  # Adam's first moment after one step is (1 - b1) x gradient
                mu = _first_moment(self.optimizer.opt_state)
                self.grad_sq = fam.leaf_sq(mu)
                self.grad_vectors = {k: v / (1.0 - opt["b1"]) for k, v in fam.vector_leaves(mu).items()}
                del mu
        self.change_sq = fam.change_from_seed_sq(cfg, self.ctx["seed"], fam.tree_leaf(self.model.params))
        self.losses = [float(x) for x in self.losses]
        self.ctx["mark"]("first_steps")
        self.grad_sq = {k: float(v) / (1.0 - opt["b1"]) ** 2 for k, v in self.grad_sq.items()}

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float, probe) -> dict:
        span, step = self.ctx["span"], self.step
        done, prev = 0, None
        snapshot = lambda: {"steps": done}  # noqa: E731
        with span("next_batch"):
            batch = self.next_batch()
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
            probe.tick(elapsed, snapshot)
            with span("train_step"):
                loss = step(batch)
            with span("next_batch"):
                batch = self.next_batch()
            if prev is not None:
                with span("wait_step"):
                    prev.block_until_ready()  # at most one step runs ahead of the host
                done += 1
            prev = loss
        if prev is not None:
            with span("wait_step"):
                prev.block_until_ready()
            done += 1
        window_s = time.perf_counter() - t0
        traced = probe.close(snapshot)
        self.window_s, self.steps, self.last_loss = window_s, done, float(prev) if prev is not None else None
        return {"seconds": window_s, "counters": {"steps": done}, "traced": traced}

    def end_to_end(self) -> dict:
        tokens = self.steps * self.batch * self.seq
        return {
            "values": {"train_tokens_per_s": tokens / self.window_s},
            "attempted": self.steps,
            "failed": 0 if self.last_loss is not None and np.isfinite(self.last_loss) else 1,
            "facts": {"steps": self.steps, "last_loss": self.last_loss, "first_losses": self.losses},
        }

    def release(self) -> None:
        self.acc.free_memory(self.model, self.optimizer, self.step)
        self.model = self.optimizer = self.step = None

    # -- the check ------------------------------------------------------------

    def check(self, control: bool = False) -> dict:
        """The program's first steps against the reference's.  ``control``
        (chipbench/tests/chip_readings.py, never a benchmark run) also reads the
        reference computed in fp8, and the reference fed half of each batch, in
        the program's place."""
        fam, cfg, seed = self.family, self.cfg, self.ctx["seed"]
        ref = reference_readings(fam, cfg, seed, self.fed, self.opt, "float32")
        got = {"losses": self.losses, "grad_sq": self.grad_sq, "change_sq": self.change_sq,
               "grad_vectors": self.grad_vectors}
        out = compare(got, ref, self.ctx["limits"])
        if control:
            low = reference_readings(fam, cfg, seed, self.fed, self.opt, "fp8")
            half = reference_readings(fam, cfg, seed, [ids[: max(1, len(ids) // 2)] for ids in self.fed], self.opt, "float32")
            out["control"] = compare(low, ref, {})
            out["half_batch"] = compare(half, ref, {})
        return out


def _first_moment(opt_state):
    """``mu`` of optax's ScaleByAdamState, wherever the chain keeps it."""
    import jax

    found = [s.mu for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0]


def reference_readings(family, cfg: dict, seed: int, fed: list, opt: dict, precision: str) -> dict:
    """The reference's losses, first-step gradient norms and parameter change
    over the fed steps, from the same seeded parameters."""
    import jax

    ref = family.Reference(cfg, precision)
    state = family.train_state(family.seeded_params(cfg, seed))
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i, ids in enumerate(fed):
            out = ref.train_step(state, ids, opt)
            losses.append(out["loss"])
            if i == 0:
                first = out
    change_sq = family.change_from_seed_sq(cfg, seed, family.state_leaf(state))
    return {"losses": losses, "grad_sq": first["grad_sq"], "change_sq": change_sq, "grad_vectors": first["grad_vectors"]}


def compare(got: dict, ref: dict, limits: dict) -> dict:
    """The numbers compared, each beside its limit.

    - ``loss_gap``: the worst step's |loss - reference| / reference.
    - ``grad_norm_gap``, ``change_norm_gap``: by the worst leaf, the gap between
      the program's norm and the reference's (not the norm of a difference),
      against the reference's norm of that leaf or of the median leaf, whichever
      is larger.  Leaves whose reference gradient is under a thousandth of the
      median leaf's are left out of the change: they move by round-off alone.
    - ``grad_vector_diff``: the norms above change only with the square of an
      unbiased error, so a lower precision hardly moves them (PERF.md).  This
      one is of first order: over the model's vectors (norm scales and biases
      of every layer, whose gradients pass through every matmul of the step),
      the worst leaf's |gradient - reference's| against the reference's norm of
      that leaf or of the median vector leaf.
    """
    loss_gaps = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
    g_ref = {k: v**0.5 for k, v in ref["grad_sq"].items()}
    c_ref = {k: v**0.5 for k, v in ref["change_sq"].items()}
    g_med, c_med = statistics.median(g_ref.values()), statistics.median(c_ref.values())
    moved = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    grad_gaps = {k: abs(got["grad_sq"][k] ** 0.5 - g_ref[k]) / max(g_ref[k], g_med) for k in g_ref}
    change_gaps = {k: abs(got["change_sq"][k] ** 0.5 - c_ref[k]) / max(c_ref[k], c_med) for k in moved}
    worst_g, worst_c = max(grad_gaps, key=grad_gaps.get), max(change_gaps, key=change_gaps.get)
    v_ref = {k: float(np.linalg.norm(v)) for k, v in ref["grad_vectors"].items()}
    v_med = statistics.median(v_ref.values())
    vector_diffs = {
        k: float(np.linalg.norm(got["grad_vectors"][k] - ref["grad_vectors"][k])) / max(v_ref[k], v_med) for k in v_ref
    }
    readings = {"loss_gap": max(loss_gaps), "grad_norm_gap": grad_gaps[worst_g], "change_norm_gap": change_gaps[worst_c],
                "grad_vector_diff": max(vector_diffs.values())}
    checks = {name: {"value": float(readings[name]), "limit": limits[name]} for name in readings if name in limits}
    for name, leaf in (("grad_norm_gap", worst_g), ("change_norm_gap", worst_c)):
        if name in checks:
            checks[name]["leaf"] = leaf
    detail = {"loss_gaps": loss_gaps, "grad_gaps": grad_gaps, "change_gaps": change_gaps, "vector_diffs": vector_diffs,
              "ref_grad_norms": g_ref, "ref_change_norms": c_ref, "ref_losses": ref["losses"]}
    return {"checks": checks, "readings": {k: float(v) for k, v in readings.items()}, "detail": detail}
