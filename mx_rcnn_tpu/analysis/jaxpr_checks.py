"""Layer 2: machine-checked TPU invariants on the real jitted steps.

Where layer 1 pattern-matches source, this layer traces the *actual*
programs — the train step ``make_train_step`` builds (donation, scan,
freeze masks and all), the eval step, and the RPN proposal-dump step —
and asserts properties of the traced/lowered artifact itself.  Everything
runs under ``JAX_PLATFORMS=cpu`` via abstract tracing + one tiny executed
step, so CI needs no accelerator; the invariants are about the program,
not the backend.

Invariants (no suppression mechanism — these must hold outright):

* ``no_x64``        — no float64/int64 aval anywhere in the traced
                      train/eval/proposal jaxprs (an x64 leak doubles
                      HBM/ICI bytes and falls off the TPU fast path).
* ``transfer_guard`` — one steady-state train step and one eval step
                      execute cleanly under
                      ``jax.transfer_guard("disallow")``: zero implicit
                      host transfers in the hot path.
* ``trace_deterministic`` — lowering the train step twice yields
                      byte-identical StableHLO: the trace is a pure
                      function of (code, shapes), not of dict ordering or
                      object identity — the in-process half of the
                      recompilation guard (utils/compile_cache.py's probe
                      is the cross-process half).
* ``donation``      — the lowered train step carries input-output
                      aliasing for the train state's buffers (donation
                      actually applied; params update in place in HBM).
* ``flop_attribution`` — >=99% of the train step's conv/dot FLOPs land in
                      a named component (utils/hlo_profile.py), so the
                      per-component MFU report has no silent "other"
                      bucket.
* ``no_f32_upcast``  — (TPU006) a bf16-mixed variant of the train step
                      (``model.backbone.dtype=bfloat16`` +
                      ``model.precision.policy=mixed``) carries no
                      bf16->f32 ``convert_element_type`` outside the
                      accumulation allowlist (:data:`UPCAST_ALLOWLIST`)
                      or the backward pass.  This is the un-rot guard
                      for the r6 mixed-precision win: one stray
                      ``.astype(jnp.float32)`` on a head output or a
                      score lane silently re-materializes the (B, ~268k)
                      detection middle in f32, and nothing else would
                      notice.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

ATTRIBUTION_MIN_PCT = 99.0


@dataclasses.dataclass
class CheckResult:
    name: str
    ok: bool
    detail: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Programs:
    """The traced surfaces under test, built once and shared by checks."""

    config_name: str
    state: Any
    train_batch: Any
    train_step: Callable
    eval_variables: Any
    eval_batch: Any
    eval_step: Callable
    proposal_step: Callable


def build_programs(config_name: str = "tiny_synthetic") -> Programs:
    """Build the real train/eval/proposal steps for ``config_name``.

    ``tiny_synthetic`` is the hermetic CPU-sized preset the test suite
    already jits; any preset works for trace-only checks but the
    transfer-guard check executes one step.
    """
    import jax

    from bench import _synthetic_batch
    from mx_rcnn_tpu.config import get_config
    from mx_rcnn_tpu.detection.graph import forward_proposals
    from mx_rcnn_tpu.parallel.step import eval_variables, make_eval_step
    from mx_rcnn_tpu.train.loop import build_all

    cfg = get_config(config_name)
    model, _tx, state, train_step, _gb = build_all(cfg, mesh=None)
    k = max(cfg.train.steps_per_call, 1)
    train_batch = _synthetic_batch(
        cfg, cfg.train.per_device_batch, cfg.data.image_size, k
    )
    pixel_stats = (cfg.data.pixel_mean, cfg.data.pixel_std)
    eval_step = make_eval_step(model, mesh=None, pixel_stats=pixel_stats)
    eval_batch = _synthetic_batch(
        cfg, cfg.train.per_device_batch, cfg.data.image_size, 1
    )
    proposal_step = jax.jit(
        lambda variables, batch: forward_proposals(
            model, variables, batch, pixel_stats=pixel_stats
        )
    )
    return Programs(
        config_name=config_name,
        state=state,
        train_batch=train_batch,
        train_step=train_step,
        eval_variables=eval_variables(state),
        eval_batch=eval_batch,
        eval_step=eval_step,
        proposal_step=proposal_step,
    )


# ---------------------------------------------------------------------------
# Jaxpr walking


def _walk_avals(jaxpr, seen: set) -> None:
    for v in list(jaxpr.invars) + list(jaxpr.constvars) + list(jaxpr.outvars):
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None:
            seen.add(str(dt))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None:
                seen.add(str(dt))
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "body_jaxpr",
                    "cond_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None:
                _walk_avals(sub.jaxpr if hasattr(sub, "jaxpr") else sub, seen)
        for br in eqn.params.get("branches", ()):
            _walk_avals(br.jaxpr, seen)


def jaxpr_dtypes(fn, *args) -> set[str]:
    """Every aval dtype appearing in ``fn(*args)``'s traced jaxpr."""
    import jax

    closed = jax.make_jaxpr(fn, static_argnums=())(*args)
    seen: set[str] = set()
    _walk_avals(closed.jaxpr, seen)
    for c in closed.consts:
        dt = getattr(c, "dtype", None)
        if dt is not None:
            seen.add(str(dt))
    return seen


# ---------------------------------------------------------------------------
# Checks


def check_no_x64(programs: Programs) -> CheckResult:
    bad: dict[str, set[str]] = {}
    surfaces = {
        "train": (programs.train_step, programs.state, programs.train_batch),
        "eval": (programs.eval_step, programs.eval_variables,
                 programs.eval_batch),
        "proposals": (programs.proposal_step, programs.eval_variables,
                      programs.eval_batch),
    }
    for name, (fn, *args) in surfaces.items():
        wide = {
            d for d in jaxpr_dtypes(fn, *args) if d in ("float64", "int64")
        }
        if wide:
            bad[name] = wide
    if bad:
        return CheckResult(
            "no_x64", False,
            "64-bit avals in traced programs: "
            + "; ".join(f"{k}: {sorted(v)}" for k, v in sorted(bad.items())),
        )
    return CheckResult(
        "no_x64", True,
        "train/eval/proposal jaxprs carry no float64/int64 avals",
    )


def check_transfer_guard(programs: Programs) -> CheckResult:
    """Execute one steady-state train step + eval step + proposal step
    under ``transfer_guard("disallow")``.

    The first call of each compiled program is run OUTSIDE the guard:
    trace-time constant transfers (e.g. the pixel-stat constants) are
    expected and happen once per compile, not per step.  Steady state must
    be implicit-transfer-free.
    """
    import jax
    import jax.numpy as jnp

    # The train step donates its input state, and the eval variables alias
    # the state's param buffers — execute on deep copies so the shared
    # Programs (reused by other checks / test fixtures) stays live.
    state = jax.tree_util.tree_map(jnp.copy, programs.state)
    train_batch = jax.device_put(programs.train_batch)
    eval_vars = jax.tree_util.tree_map(jnp.copy, programs.eval_variables)
    eval_batch = jax.device_put(programs.eval_batch)

    # Warm-up/compile round (guard off).
    state2, _ = programs.train_step(state, train_batch)
    programs.eval_step(eval_vars, eval_batch)
    programs.proposal_step(eval_vars, eval_batch)
    try:
        with jax.transfer_guard("disallow"):
            _state3, metrics = programs.train_step(state2, train_batch)
            dets = programs.eval_step(eval_vars, eval_batch)
            props = programs.proposal_step(eval_vars, eval_batch)
            jax.block_until_ready((metrics, dets.valid, props.valid))
    except Exception as e:  # jaxlib raises backend-specific error types
        return CheckResult(
            "transfer_guard", False,
            f"implicit transfer in steady-state step: {type(e).__name__}: "
            f"{str(e)[:300]}",
        )
    return CheckResult(
        "transfer_guard", True,
        "steady-state train/eval/proposal steps execute under "
        "transfer_guard('disallow')",
    )


def check_trace_deterministic(programs: Programs) -> CheckResult:
    import hashlib

    def lower_hash() -> str:
        txt = programs.train_step.lower(
            programs.state, programs.train_batch
        ).as_text()
        return hashlib.sha256(txt.encode()).hexdigest()

    h1, h2 = lower_hash(), lower_hash()
    if h1 != h2:
        return CheckResult(
            "trace_deterministic", False,
            f"two lowerings of the train step differ ({h1[:12]} vs "
            f"{h2[:12]}) — trace depends on dict order / object identity "
            "and will recompile per process",
        )
    return CheckResult(
        "trace_deterministic", True,
        f"double-lower StableHLO hash stable ({h1[:12]})",
    )


def check_donation(programs: Programs) -> CheckResult:
    import jax

    txt = programs.train_step.lower(
        programs.state, programs.train_batch
    ).as_text()
    aliased = txt.count("tf.aliasing_output")
    param_leaves = len(jax.tree_util.tree_leaves(programs.state.params))
    if aliased < param_leaves:
        return CheckResult(
            "donation", False,
            f"only {aliased} aliased inputs in the lowered train step for "
            f"{param_leaves} param leaves — state donation not applied "
            "(params would double-buffer in HBM)",
        )
    return CheckResult(
        "donation", True,
        f"{aliased} donated input buffers cover the train state "
        f"({param_leaves} param leaves)",
    )


def check_flop_attribution(programs: Programs) -> CheckResult:
    from mx_rcnn_tpu.utils.hlo_profile import attribute_flops

    acc = attribute_flops(
        programs.train_step, programs.state, programs.train_batch
    )
    total = sum(v["flops"] for v in acc.values())
    if not total:
        return CheckResult(
            "flop_attribution", False, "no conv/dot FLOPs found in the "
            "train step trace (attribution walk broken?)",
        )
    other = acc.get("other", {"flops": 0.0})["flops"]
    pct = 100.0 * (total - other) / total
    if pct < ATTRIBUTION_MIN_PCT:
        return CheckResult(
            "flop_attribution", False,
            f"only {pct:.2f}% of train-step MXU FLOPs attributed to a "
            f"named component (need >={ATTRIBUTION_MIN_PCT}%); 'other' "
            f"holds {other / 1e9:.2f} GFLOP — tag the emitting code with "
            "jax.named_scope or extend hlo_profile.COMPONENT_PATTERNS",
        )
    return CheckResult(
        "flop_attribution", True,
        f"{pct:.2f}% of train-step MXU FLOPs attributed "
        f"({len([c for c in acc if c != 'other'])} components)",
    )


# ---------------------------------------------------------------------------
# TPU006: no accidental f32 upcast on the bf16 hot path


# Name-stack tokens under which a bf16->f32 convert is an ACCUMULATION
# entry, not a leak: losses, sampling/assignment (IoU vs f32 gt boxes),
# proposal decode (f32 anchors/coords — see utils/precision.py's box-
# coordinate note), ROI Align (f32 bilinear weights from f32 roi coords
# and an f32 per-bin sample accumulator, downcast ONCE to the feature
# dtype on exit — ops/roi_align.py), the guardian finiteness reduction,
# and the optimizer.  The backward pass is allowed wholesale via its
# "transpose(...)" stack frames: jax.grad of an f32 param used in bf16
# compute accumulates the gradient back to f32 through the transpose of
# the param cast — that convert IS the f32-master-gradient contract, not
# a leak.
UPCAST_ALLOWLIST = (
    "rpn_loss",
    "rcnn_loss",
    "mask_loss",
    "guardian",
    "optimizer",
    "proposals",
    "sample_rois",
    "assign_anchors",
    "roi_align",
)

_BF16_OVERRIDES = (
    "model.backbone.dtype=bfloat16",
    "model.precision.policy=mixed",
)


@functools.lru_cache(maxsize=2)
def _bf16_train_jaxpr(config_name: str):
    """Traced jaxpr of the train step under the bf16 "mixed" policy.

    The shared ``Programs`` trace the preset as-is — for tiny_synthetic
    (f32 backbone) the mixed policy degenerates to all-f32 and an upcast
    scan would be vacuous — so TPU006 traces its own bf16 variant.
    Memoized: the trace is the expensive part and both the CLI and the
    test suite call this."""
    import jax

    from bench import _synthetic_batch
    from mx_rcnn_tpu.config import apply_overrides, get_config
    from mx_rcnn_tpu.train.loop import build_all

    cfg = apply_overrides(get_config(config_name), list(_BF16_OVERRIDES))
    _model, _tx, state, train_step, _gb = build_all(cfg, mesh=None)
    k = max(cfg.train.steps_per_call, 1)
    batch = _synthetic_batch(
        cfg, cfg.train.per_device_batch, cfg.data.image_size, k
    )
    return jax.make_jaxpr(train_step)(state, batch)


def _walk_upcasts(jaxpr, prefix: str, bad: list[str], total: list[int]) -> None:
    for eqn in jaxpr.eqns:
        stack = str(getattr(eqn.source_info, "name_stack", "") or "")
        full = "/".join(s for s in (prefix, stack) if s)
        if eqn.primitive.name == "convert_element_type":
            in_dt = str(getattr(eqn.invars[0].aval, "dtype", ""))
            out_dt = str(getattr(eqn.outvars[0].aval, "dtype", ""))
            if in_dt == "bfloat16" and out_dt == "float32":
                total[0] += 1
                if "transpose(" not in full and not any(
                    tok in full for tok in UPCAST_ALLOWLIST
                ):
                    bad.append(full or "<no name stack>")
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "body_jaxpr",
                    "cond_jaxpr"):
            sub = eqn.params.get(key)
            if sub is not None:
                _walk_upcasts(
                    sub.jaxpr if hasattr(sub, "jaxpr") else sub,
                    full, bad, total,
                )
        for br in eqn.params.get("branches", ()):
            _walk_upcasts(br.jaxpr, full, bad, total)


def check_no_f32_upcast(programs: Programs) -> CheckResult:
    """TPU006: every bf16->f32 convert in the bf16-mixed train step sits
    under an allowlisted accumulation scope or the backward pass."""
    closed = _bf16_train_jaxpr(programs.config_name)
    bad: list[str] = []
    total = [0]
    _walk_upcasts(closed.jaxpr, "", bad, total)
    if bad:
        sample = sorted(set(bad))[:8]
        return CheckResult(
            "no_f32_upcast", False,
            f"{len(bad)} bf16->f32 convert(s) outside the accumulation "
            f"allowlist {UPCAST_ALLOWLIST} in the bf16-mixed train step; "
            "name stacks: " + "; ".join(s[:90] for s in sample),
        )
    return CheckResult(
        "no_f32_upcast", True,
        f"all {total[0]} bf16->f32 converts in the bf16-mixed train step "
        "sit under allowlisted accumulation scopes or the backward pass",
    )


ALL_CHECKS = (
    check_no_x64,
    check_trace_deterministic,
    check_donation,
    check_flop_attribution,
    check_no_f32_upcast,
    check_transfer_guard,   # last: the only one that executes the programs
)


def run_jaxpr_checks(
    config_name: str = "tiny_synthetic",
    programs: Optional[Programs] = None,
) -> list[CheckResult]:
    """Run every layer-2 invariant; returns one CheckResult per check.

    A check that *errors* (as opposed to failing its assertion) is
    reported as failed with the exception — a broken checker must never
    read as a passing invariant.
    """
    if programs is None:
        programs = build_programs(config_name)
    results = []
    for check in ALL_CHECKS:
        try:
            results.append(check(programs))
        except Exception as e:
            results.append(
                CheckResult(
                    check.__name__.removeprefix("check_"), False,
                    f"checker raised {type(e).__name__}: {str(e)[:300]}",
                )
            )
    return results
