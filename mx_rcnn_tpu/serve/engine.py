"""Robust inference runtime around the jitted inference step.

The jitted graphs (detection/graph.py) are fast but brittle to operate:
an unexpected image shape silently triggers a multi-second recompile, a
hung device call blocks forever, and a burst of requests queues without
bound.  :class:`InferenceEngine` wraps them with the serving behaviors a
production endpoint needs:

* **Startup warmup** — every (mode, resolution-bucket) program is
  compiled before the engine reports ready; a request can never pay a
  compile.
* **Bucketed pad-batching** — requests letterbox into a fixed set of
  resolution buckets and pad into static batch shapes, so arbitrary
  request sizes never create new programs (enforced, not hoped:
  :class:`DetectorRunner` refuses shapes outside the warmed set).
* **Admission control** — a bounded queue; when it is full the request
  is shed immediately with a typed :class:`Overloaded` instead of
  queueing into certain deadline death.
* **Per-request deadlines** — expired requests fail fast with
  :class:`DeadlineExceeded`; remaining budget drives the degradation
  ladder (serve/degrade.py) so tight deadlines get a cheaper program
  instead of a guaranteed miss.
* **Watchdog** — a monitor thread detects a device call that stopped
  returning (a hung runtime) and fails the engine to DEAD
  so supervisors replace the process instead of black-holing traffic.
* **Continuous batching** (``batch_size > 1`` + ``pack``) — pending
  requests from different callers pack into every bucket slot of each
  device call (serve/batcher.py), deadline-aware, one compiled program
  per call; each de-interleaved response is bitwise identical to the
  one-request-per-call path.  The worker holds at most ``2 *
  batch_size`` requests out of the admission queue, so shed semantics
  stay bounded; ``pack_window_s`` optionally lingers for stragglers to
  top off a partial batch.

The engine is generic over a ``runner`` (anything with ``buckets``,
``levels()``, ``batch_size``, ``pick_bucket`` and ``run``); the real
JAX-backed implementation is :class:`DetectorRunner`, and tests drive the
same engine with deterministic fakes.
"""

from __future__ import annotations

import logging
import queue as queue_mod
import threading
import time
from typing import Callable, NamedTuple, Optional, Sequence

import numpy as np

from mx_rcnn_tpu import obs
from mx_rcnn_tpu.serve import health as health_mod
from mx_rcnn_tpu.serve import tenancy as tenancy_mod
from mx_rcnn_tpu.serve.batcher import PackBuffer
from mx_rcnn_tpu.serve.degrade import (
    FULL_QUALITY_LEVELS,
    CircuitBreaker,
    HysteresisPlanner,
    LatencyEstimator,
)

log = logging.getLogger("mx_rcnn_tpu.serve")


class ServeError(RuntimeError):
    """Base class for typed serving failures."""


class Overloaded(ServeError):
    """Admission control shed this request: the queue is full."""


class DeadlineExceeded(ServeError):
    """The request's deadline passed before a result was produced."""


class EngineUnavailable(ServeError):
    """The engine cannot serve (not started, stopped, or declared dead)."""


class QuotaExceeded(ServeError):
    """The caller's tenant is over its token-bucket quota
    (serve/tenancy.py).  Distinct from :class:`Overloaded` on purpose:
    quota is the tenant's own budget, not fleet pressure — it maps to
    429 + Retry-After on the wire and never feeds the autoscaler's
    shed-rate signal."""

    retry_after_s: float = 1.0  # wire hint; admission sets the real value


class Plan(NamedTuple):
    level: str              # degrade.LEVELS entry
    mode: str               # program family: full | reduced | proposals
    bucket: tuple[int, int]  # compiled canvas (H, W)


class InferenceRequest:
    """A submitted request; ``result()`` blocks until served or failed."""

    __slots__ = ("image", "enqueued_at", "deadline", "_event", "_result",
                 "_error", "plan", "_callbacks", "_cb_lock",
                 "trace_id", "span", "queue_span", "tenant")

    def __init__(self, image: np.ndarray, enqueued_at: float,
                 deadline: Optional[float]) -> None:
        self.image = image
        self.enqueued_at = enqueued_at
        self.deadline = deadline
        # Resolved tenant name (serve/tenancy.py) — None on the
        # single-tenant path; the batcher folds None to the default.
        self.tenant: Optional[str] = None
        self._event = threading.Event()
        self._result: Optional[dict] = None
        self._error: Optional[BaseException] = None
        self.plan: Optional[Plan] = None
        self._callbacks: list[Callable[["InferenceRequest"], None]] = []
        self._cb_lock = threading.Lock()
        # Tracing state (obs/tracing.py): set by submit() when span
        # recording is on; _finish() closes whatever is still open so
        # every completion path — served, shed, deadline, engine death —
        # ends the request's span tree exactly once.
        self.trace_id: Optional[str] = None
        self.span = None
        self.queue_span = None

    def _set_result(self, result: dict) -> None:
        self._result = result
        self._finish()

    def _set_error(self, error: BaseException) -> None:
        self._error = error
        self._finish()

    def _finish(self) -> None:
        if self.queue_span is not None:
            self.queue_span.end()
        if self.span is not None:
            if self._error is not None:
                self.span.set(error=type(self._error).__name__)
            self.span.end()
        self._event.set()
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for cb in cbs:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 - a callback must not kill
                log.exception("request done-callback raised")  # the worker

    def add_done_callback(
        self, fn: Callable[["InferenceRequest"], None]
    ) -> None:
        """Call ``fn(request)`` exactly once when the request completes
        (success or failure); immediately if it already did.  The fleet
        router uses this to wake hedging watchers without polling."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def error(self) -> Optional[BaseException]:
        """The failure, if the request is done and failed (non-blocking)."""
        return self._error if self._event.is_set() else None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until done (or ``timeout``); True when complete."""
        return self._event.wait(timeout)

    def result(self, timeout: Optional[float] = None) -> dict:
        """The served detections dict (boxes/scores/classes/level/...);
        raises the typed serving error on failure.  The watchdog bounds
        how long an un-timed wait can last."""
        if not self._event.wait(timeout):
            raise TimeoutError("request not complete")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


class DetectorRunner:
    """JAX-backed runner: compiled programs over fixed shape buckets.

    Programs (all compiled at warmup, none ever added after):
      * ``("full", bucket)`` for EVERY bucket — the production detector.
      * ``("full_q8", bucket)`` for EVERY bucket — int8/bf16 box head
        (serve/quantize.py), when built with ``int8_head=True``.
        Quantization degrades precision, not resolution, so q8 requests
        keep their own shape bucket instead of being letterboxed down.
      * ``("full_q8n", bucket)`` for EVERY bucket — full-network
        weight-only int8 (backbone/FPN/RPN/head), when built with
        ``int8_network=True``.  Same per-bucket reasoning.
      * ``("reduced", smallest bucket)`` — ``reduced_max_detections``
        output slots (cheaper postprocess/NMS).
      * ``("proposals", smallest bucket)`` — RPN-only, class-agnostic.

    ``run`` letterboxes each request image into the plan's bucket, pads
    the micro-batch to the static ``batch_size``, executes, and maps
    boxes back to original image coordinates.  Any (mode, bucket) pair
    outside the warmed set is a hard error — the no-recompile guarantee
    is enforced here rather than discovered in a latency graph.

    **Double-buffered weights**: the live params (and quantized head)
    ride one ``_active`` tuple; :meth:`swap_weights` transfers the new
    tree to the device and blocks until it is resident *while the live
    buffer keeps serving*, then flips the tuple — a single reference
    assignment, so a concurrent ``run`` sees entirely-old or
    entirely-new weights, never a mix.  Every result carries the
    ``generation`` that served it.

    ``device`` pins the runner to one chip (replica-per-chip fleets,
    serve/fleet.py): params commit there via the execution plan's
    ``place`` and the jitted programs follow them.
    """

    def __init__(
        self,
        cfg,
        variables,
        buckets: Optional[Sequence[tuple[int, int]]] = None,
        batch_size: int = 1,
        reduced_max_detections: Optional[int] = None,
        with_proposals: bool = True,
        int8_head: bool = False,
        int8_network: bool = False,
        device: Optional[object] = None,
    ) -> None:
        import dataclasses

        import jax

        from mx_rcnn_tpu.detection import TwoStageDetector

        self.cfg = cfg
        self.batch_size = int(batch_size)
        bks = list(buckets) if buckets else [tuple(cfg.data.image_size)]
        # Ascending by area; pick_bucket takes the first that fits.
        self.buckets = sorted(
            (tuple(int(x) for x in b) for b in bks),
            key=lambda b: (b[0] * b[1], b),
        )
        if reduced_max_detections is None:
            reduced_max_detections = max(1, cfg.model.test.max_detections // 4)
        self.reduced_max_detections = int(reduced_max_detections)
        stats = (cfg.data.pixel_mean, cfg.data.pixel_std)

        model_cfg = cfg.model
        model = TwoStageDetector(cfg=model_cfg)
        reduced_cfg = dataclasses.replace(
            model_cfg,
            test=dataclasses.replace(
                model_cfg.test,
                max_detections=self.reduced_max_detections,
                fused_top_k=min(
                    cfg.model.test.fused_top_k,
                    4 * self.reduced_max_detections,
                ),
            ),
        )
        reduced_model = TwoStageDetector(cfg=reduced_cfg)

        from mx_rcnn_tpu.detection.graph import (
            forward_inference,
            forward_proposals,
        )

        # One jitted callable per MODE; buckets become distinct XLA
        # programs of the same callable (different static shapes).  All
        # compile through the execution plan (parallel/plan.py) — the
        # same scaffolding the train/eval steps use; serving runs the
        # plan's mesh-less form (plain jit, optionally pinned to one
        # replica chip), and a sharded server is one ``mesh=`` away
        # rather than a rewrite.
        from mx_rcnn_tpu.parallel.plan import ExecutionPlan

        plan = ExecutionPlan(mesh=None, device=device)
        self._plan = plan
        self.device = device
        self._steps = {
            "full": plan.compile_infer(
                lambda v, b: forward_inference(model, v, b, pixel_stats=stats)
            ),
            "reduced": plan.compile_infer(
                lambda v, b: forward_inference(
                    reduced_model, v, b, pixel_stats=stats
                )
            ),
            "proposals": plan.compile_infer(
                lambda v, b: forward_proposals(model, v, b, pixel_stats=stats)
            ),
        }
        self._program_keys = [("full", b) for b in self.buckets]
        self._int8_head = bool(int8_head)
        if int8_head:
            from mx_rcnn_tpu.serve.quantize import apply_box_head_q8

            # The quantized tree rides as a jit ARGUMENT (device buffers),
            # not a closure — same request-size reasoning as the params,
            # and swap_weights can re-quantize and flip it atomically
            # alongside them.  Mesh-less plan compile == plain jit, so
            # the extra operand is fine; a sharded plan would need its
            # own spec.
            self._q8_step = plan.compile_infer(
                lambda v, q, b: forward_inference(
                    model, v, b, pixel_stats=stats,
                    box_head_apply=lambda pooled: apply_box_head_q8(
                        q, pooled
                    ),
                )
            )
            # Per-bucket like "full": quantization trades precision, not
            # resolution, so a q8 request must not be silently
            # letterboxed into the smallest shape.
            self._program_keys += [("full_q8", b) for b in self.buckets]
        self._int8_network = bool(int8_network)
        if int8_network:
            from mx_rcnn_tpu.serve.quantize import dequantize_network

            # The whole variables tree is replaced by its int8/scale
            # form and reconstructed IN-GRAPH — the program body is the
            # production forward_inference, unchanged; only the weight
            # operand shrinks 4x.
            self._q8n_step = plan.compile_infer(
                lambda qn, b: forward_inference(
                    model, dequantize_network(qn), b, pixel_stats=stats
                )
            )
            self._program_keys += [("full_q8n", b) for b in self.buckets]
        # Live weight buffers: (params, quantized head | None, quantized
        # network | None, generation).  One tuple so the swap flip is a
        # single reference assignment.
        self._active = (
            plan.place(variables), self._quantized(variables),
            self._quantized_net(variables), 0,
        )
        if with_proposals:
            self._program_keys += [
                ("reduced", self.buckets[0]),
                ("proposals", self.buckets[0]),
            ]
        else:
            self._program_keys += [("reduced", self.buckets[0])]
        self._warmed: set[tuple[str, tuple[int, int]]] = set()

    # -- weights ----------------------------------------------------------

    def _quantized(self, variables):
        """Quantize + place the box head for the q8 program (or None)."""
        if not self._int8_head:
            return None
        from mx_rcnn_tpu.serve.quantize import quantize_box_head

        return self._plan.place(quantize_box_head(variables))

    def _quantized_net(self, variables):
        """Quantize + place the whole network for q8n (or None)."""
        if not self._int8_network:
            return None
        from mx_rcnn_tpu.serve.quantize import quantize_network

        return self._plan.place(quantize_network(variables))

    @property
    def generation(self) -> int:
        """Monotonic weight-swap counter; 0 = the construction weights."""
        return self._active[3]

    def swap_weights(self, variables, generation: Optional[int] = None) -> int:
        """Zero-downtime weight swap: warm the standby buffer, then flip.

        The new tree (and re-quantized int8 head, when enabled) is
        transferred to the replica device and blocked-until-resident
        while the live buffer keeps serving; the flip is one tuple
        assignment, so no request ever executes against a half-swapped
        tree.  The compiled programs are untouched — identical
        shapes/dtypes are enforced below, so the swap can never trigger
        a recompile on the serving path.  Returns the new generation
        (``generation`` overrides the default +1 — the fleet uses it to
        align a rebuilt replica with the fleet generation).
        """
        import jax

        live_vars, _, _, live_gen = self._active
        flat_new = jax.tree_util.tree_flatten(variables)
        flat_live = jax.tree_util.tree_flatten(live_vars)
        if flat_new[1] != flat_live[1]:
            raise ValueError(
                "swap_weights: new tree structure differs from the live "
                "tree — a swap must not change the compiled programs"
            )
        def sig(x):  # no np.asarray: must not device_get the live tree
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return (tuple(x.shape), str(x.dtype))
            arr = np.asarray(x)
            return (arr.shape, str(arr.dtype))

        for new, old in zip(flat_new[0], flat_live[0]):
            if sig(new) != sig(old):
                raise ValueError(
                    f"swap_weights: leaf shape/dtype drift "
                    f"{sig(old)} -> {sig(new)} — a swap must not change "
                    "the compiled programs"
                )
        new_vars = self._plan.place(variables)
        new_q8 = self._quantized(variables)
        new_q8n = self._quantized_net(variables)
        # Warm the standby buffer: the transfer completes (device-resident
        # HBM) before the flip, so the first post-flip request pays zero
        # copy latency.
        jax.block_until_ready(
            tuple(t for t in (new_vars, new_q8, new_q8n) if t is not None)
        )
        gen = live_gen + 1 if generation is None else int(generation)
        if gen <= live_gen:
            raise ValueError(
                f"swap_weights: generation must be monotonic "
                f"({live_gen} -> {gen})"
            )
        self._active = (new_vars, new_q8, new_q8n, gen)
        return gen

    # -- engine-facing surface --------------------------------------------

    def levels(self) -> tuple[str, ...]:
        out = ["full"]
        if len(self.buckets) > 1:
            out.append("small")
        if any(m == "full_q8" for m, _ in self._program_keys):
            out.append("full_q8")
        if any(m == "full_q8n" for m, _ in self._program_keys):
            out.append("full_q8n")
        out.append("reduced")
        if any(m == "proposals" for m, _ in self._program_keys):
            out.append("proposals")
        return tuple(out)

    def pick_bucket(self, height: int, width: int) -> tuple[int, int]:
        """Smallest bucket that holds the image without downscaling; the
        largest bucket otherwise (letterbox downscales into it)."""
        for b in self.buckets:
            if b[0] >= height and b[1] >= width:
                return b
        return self.buckets[-1]

    def smaller_bucket(
        self, bucket: tuple[int, int]
    ) -> Optional[tuple[int, int]]:
        i = self.buckets.index(bucket)
        return self.buckets[i - 1] if i > 0 else None

    def warmup(self) -> int:
        """Compile every program with a zero batch; returns program count."""
        import jax

        variables, box_q8, net_q8, _ = self._active
        for mode, bucket in self._program_keys:
            batch = self._make_batch(
                np.zeros((self.batch_size, *bucket, 3), np.float32),
                np.tile(
                    np.asarray([bucket], np.float32), (self.batch_size, 1)
                ),
            )
            if mode == "full_q8":
                out = self._q8_step(variables, box_q8, batch)
            elif mode == "full_q8n":
                out = self._q8n_step(net_q8, batch)
            else:
                out = self._steps[mode](variables, batch)
            jax.block_until_ready(out)
            self._warmed.add((mode, bucket))
        return len(self._warmed)

    def run(self, mode: str, bucket: tuple[int, int],
            images: Sequence[np.ndarray]) -> list[dict]:
        if (mode, bucket) not in self._warmed:
            raise EngineUnavailable(
                f"program ({mode}, {bucket}) was never warmed — refusing "
                "to compile on the serving path"
            )
        if len(images) > self.batch_size:
            raise ValueError(
                f"micro-batch of {len(images)} exceeds batch_size "
                f"{self.batch_size}"
            )
        import jax

        from mx_rcnn_tpu.data.transforms import letterbox, normalize_image

        # One read of the live buffers: the whole micro-batch executes
        # against a consistent (params, q8, q8n, generation) snapshot
        # even if swap_weights flips mid-call.
        variables, box_q8, net_q8, generation = self._active
        rows, hw, scales, orig = [], [], [], []
        for img in images:
            h, w = img.shape[:2]
            canvas, _, scale, (nh, nw) = letterbox(
                img.astype(np.float32),
                np.zeros((0, 4), np.float32),
                bucket,
                min(bucket),
                max(bucket),
            )
            rows.append(
                normalize_image(
                    canvas, self.cfg.data.pixel_mean, self.cfg.data.pixel_std
                )
            )
            hw.append([nh, nw])
            scales.append(scale)
            orig.append((h, w))
        pad = self.batch_size - len(rows)
        if pad:
            rows += [np.zeros_like(rows[0])] * pad
            hw += [list(bucket)] * pad
        batch = self._make_batch(
            np.stack(rows), np.asarray(hw, np.float32)
        )
        if mode == "full_q8":
            out = jax.device_get(self._q8_step(variables, box_q8, batch))
        elif mode == "full_q8n":
            out = jax.device_get(self._q8n_step(net_q8, batch))
        else:
            out = jax.device_get(self._steps[mode](variables, batch))
        results = [
            self._postprocess(mode, out, i, scales[i], *orig[i])
            for i in range(len(images))
        ]
        for res in results:
            res["generation"] = generation
        return results

    # -- internals ---------------------------------------------------------

    def _make_batch(self, images: np.ndarray, image_hw: np.ndarray):
        from mx_rcnn_tpu.detection import Batch

        g = self.cfg.data.max_gt_boxes
        b = images.shape[0]
        return Batch(
            images=images,
            image_hw=image_hw,
            gt_boxes=np.zeros((b, g, 4), np.float32),
            gt_classes=np.zeros((b, g), np.int32),
            gt_valid=np.zeros((b, g), bool),
        )

    def _postprocess(self, mode, out, i, scale, height, width) -> dict:
        from mx_rcnn_tpu.evalutil.postprocess import unletterbox_detections

        if mode == "proposals":
            valid = np.asarray(out.valid[i])
            boxes = np.asarray(out.rois[i])[valid] / max(scale, 1e-12)
            boxes[:, 0::2] = np.clip(boxes[:, 0::2], 0, width - 1)
            boxes[:, 1::2] = np.clip(boxes[:, 1::2], 0, height - 1)
            return {
                "boxes": boxes.astype(np.float32),
                "scores": np.asarray(out.scores[i])[valid],
                "classes": np.zeros(int(valid.sum()), np.int32),
            }
        return unletterbox_detections(
            out.boxes[i], out.scores[i], out.classes[i], out.valid[i],
            scale, height, width,
            masks=out.masks[i] if getattr(out, "masks", None) is not None
            else None,
        )


class InferenceEngine:
    """Bounded-queue serving loop over a runner's compiled programs.

    Lifecycle: construct → ``start()`` (warms every program, then spawns
    the worker + watchdog threads and reports READY) → ``submit``/
    ``infer`` → ``stop()``.  Usable as a context manager.
    """

    _STOP = object()

    def __init__(
        self,
        runner,
        max_queue: int = 16,
        default_timeout: Optional[float] = None,
        hang_timeout: float = 60.0,
        watchdog_poll: float = 0.25,
        headroom: float = 1.25,
        up_margin: float = 1.5,
        up_dwell: int = 3,
        breaker: Optional[CircuitBreaker] = None,
        replica_id: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        pack: bool = True,
        pack_window_s: float = 0.0,
        tenancy=None,
        tenancy_admit: bool = True,
    ) -> None:
        self.runner = runner
        self._clock = clock
        # Multi-tenancy (serve/tenancy.py): the shared TenancyPolicy, or
        # None for the single-tenant path (metric series stay
        # bit-identical).  ``tenancy_admit`` is False when an outer
        # admission layer (serve/fleet.py) already charged the quota —
        # the engine then only uses the policy for labels and
        # weighted-fair packing, never double-charging a request.
        self._tenancy = tenancy
        self._tenancy_admit = bool(tenancy_admit) and tenancy is not None
        # Continuous batching is only meaningful with slots to fill; at
        # batch_size == 1 the legacy take path is byte-for-byte the same
        # behavior with less machinery, so keep it.
        self._pack = bool(pack) and runner.batch_size > 1
        self.pack_window_s = float(pack_window_s)
        self.default_timeout = default_timeout
        self.hang_timeout = hang_timeout
        self.watchdog_poll = watchdog_poll
        self.headroom = headroom
        self.breaker = breaker or CircuitBreaker(clock=clock)
        self.estimates = LatencyEstimator()
        self.planner = HysteresisPlanner(
            headroom=headroom, up_margin=up_margin, up_dwell=up_dwell
        )
        self.replica_id = replica_id
        self._mlabels = {
            "replica": "-" if replica_id is None else str(replica_id)
        }
        self.health = health_mod.EngineHealth(
            clock=clock, replica_id=replica_id
        )
        self._queue: queue_mod.Queue = queue_mod.Queue(maxsize=max_queue)
        self._carry = None  # InferenceRequest | _STOP carried across takes
        # Planned requests awaiting a pack; tenancy makes the pack
        # composition weighted-fair (serve/batcher.py).
        self._buf = PackBuffer(tenancy=self._tenancy)
        self._stop_parked = False  # STOP seen; buffer flushes first
        self._occ_calls = 0        # device calls (occupancy denominator)
        self._occ_filled = 0       # request slots filled across them
        self._inflight_since: Optional[float] = None
        self._inflight_plan: Optional[Plan] = None
        self._inflight_reqs: list[InferenceRequest] = []
        self._lock = threading.Lock()
        self._started = False
        self._draining = False  # no new admissions; accepted work flushes
        self._stopping = False  # the worker must exit
        self._worker: Optional[threading.Thread] = None
        self._watchdog: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "InferenceEngine":
        if self._started:
            return self
        try:
            n = self.runner.warmup()
        except Exception as e:
            self.health.transition(
                health_mod.DEAD, f"warmup failed: {type(e).__name__}: {e}"
            )
            raise
        log.info(
            "engine ready: %d compiled programs, buckets=%s, levels=%s",
            n, list(self.runner.buckets), list(self.runner.levels()),
        )
        self._started = True
        self.health.transition(health_mod.READY, "warmup complete")
        self._worker = threading.Thread(
            target=self._worker_loop, name="serve-worker", daemon=True
        )
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, name="serve-watchdog", daemon=True
        )
        self._worker.start()
        self._watchdog.start()
        return self

    def stop(self, timeout: float = 10.0, drain: bool = True) -> None:
        """Shut down.  With ``drain`` (the default) admission stops
        FIRST, the worker flushes every already-accepted request, and
        only then is any residue failed — an accepted request is a
        promise, and a routine stop must not break it.  ``drain=False``
        is the fast path: queued requests fail immediately with
        ``EngineUnavailable("engine stopping")`` (typed as a shutdown,
        not a serving failure, so fleet retry logic can tell them
        apart)."""
        if not self._started or self._stopping:
            return
        self._draining = True  # submit() refuses from here on
        if not drain:
            self._stopping = True
        try:
            # Blocking put: FIFO places the sentinel BEHIND every
            # accepted request, so a draining worker flushes them all
            # before it sees the stop.
            self._queue.put(self._STOP, timeout=timeout)
        except queue_mod.Full:
            pass
        if self._worker is not None:
            self._worker.join(timeout)
        self._stopping = True
        self._fail_pending(EngineUnavailable("engine stopping"))
        self.health.transition(health_mod.DEAD, "stopped")
        if self._watchdog is not None:
            self._watchdog.join(timeout)

    def kill(self, reason: str = "killed") -> None:
        """Hard-fail the engine: DEAD now, every in-flight and queued
        request fails with a typed error.  The fleet router uses this to
        fence a quarantined replica (waiters fail fast and retry on a
        healthy one); chaos scenarios use it as the crash injection."""
        self.health.transition(health_mod.DEAD, reason)
        obs.emit("serve", "engine_killed", {"reason": reason}, logger=log)
        obs.flight_dump(
            "engine_killed", {"replica": self.replica_id, "reason": reason}
        )
        error = EngineUnavailable(f"engine died: {reason}")
        with self._lock:
            stuck = list(self._inflight_reqs)
        for r in stuck:
            r._set_error(error)
        self._fail_pending(error)

    def swap_weights(
        self, variables, generation: Optional[int] = None
    ) -> int:
        """Zero-downtime weight swap, delegated to the runner (standby
        warm + atomic flip) and recorded in the health snapshot.  Safe
        under live traffic."""
        gen = self.runner.swap_weights(variables, generation=generation)
        self.health.record_swap(gen)
        return gen

    def __enter__(self) -> "InferenceEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client API --------------------------------------------------------

    def submit(
        self, image: np.ndarray, timeout: Optional[float] = None,
        trace_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
        tenant: Optional[str] = None,
    ) -> InferenceRequest:
        """Enqueue one image; returns immediately.  Raises
        :class:`Overloaded` when the queue is full,
        :class:`QuotaExceeded` when a standalone engine's tenancy policy
        rejects the tenant, or :class:`EngineUnavailable` when the
        engine cannot serve.  ``trace_id``/``parent_span_id`` link the
        request's spans under a caller's trace (the fleet router passes
        its attempt span)."""
        if not self._started:
            raise EngineUnavailable("engine not started")
        if self._draining or self._stopping:
            raise EngineUnavailable("engine stopping")
        if not self.health.alive():
            raise EngineUnavailable(
                f"engine is dead: {self.health.reason}"
            )
        if self._tenancy is not None:
            tenant = self._tenancy.resolve(tenant)
            if self._tenancy_admit and not self._tenancy.admit(tenant):
                tlabel = self._tenancy.label(tenant)
                obs.counter(
                    "serve_quota_exceeded_total",
                    "requests rejected by per-tenant quota",
                ).inc(tenant=tlabel, **self._mlabels)
                obs.emit("serve", "tenant_quota_exceeded", {
                    "tenant": tlabel, "layer": "engine",
                }, logger=log)
                err = QuotaExceeded(f"tenant {tenant!r} over quota")
                err.retry_after_s = self._tenancy.retry_after_s(tenant)
                raise err
        now = self._clock()
        timeout = self.default_timeout if timeout is None else timeout
        req = InferenceRequest(
            image, now, None if timeout is None else now + timeout
        )
        req.tenant = tenant
        req.trace_id = trace_id
        if obs.spans_enabled():
            req.span = obs.span(
                "engine_request", subsystem="serve", trace_id=trace_id,
                parent_id=parent_span_id, attrs=dict(self._mlabels),
            )
            req.trace_id = req.span.trace_id
            req.queue_span = req.span.child("queue")
        try:
            self._queue.put_nowait(req)
        except queue_mod.Full:
            self.health.record_shed()
            self._note_pressure()
            obs.counter(
                "serve_shed_total", "requests shed by admission control"
            ).inc(**self._req_labels(tenant))
            obs.emit("serve", "shed", {
                "queue_depth": self._queue.qsize(),
                "max_queue": self._queue.maxsize,
            }, logger=log)
            if req.queue_span is not None:
                req.queue_span.end()
            if req.span is not None:
                req.span.end(error="Overloaded")
            raise Overloaded(
                f"queue full ({self._queue.maxsize} waiting); request shed"
            ) from None
        obs.counter(
            "serve_requests_total", "requests admitted"
        ).inc(**self._req_labels(tenant))
        obs.gauge(
            "serve_queue_depth", "accepted-but-unserved requests"
        ).set(self._queue.qsize(), **self._mlabels)
        return req

    def _req_labels(self, tenant: Optional[str]) -> dict:
        """Per-request metric labels: replica always; tenant only when
        tenancy is configured (series stay bit-identical otherwise),
        folded to the bounded vocabulary by the policy."""
        if self._tenancy is None:
            return self._mlabels
        return dict(self._mlabels, tenant=self._tenancy.label(tenant))

    def infer(
        self, image: np.ndarray, timeout: Optional[float] = None
    ) -> dict:
        return self.submit(image, timeout).result()

    @property
    def queue_depth(self) -> int:
        """Accepted-but-unserved request count (router load signal);
        includes requests pooled in the pack buffer."""
        return self._queue.qsize() + len(self._buf)

    def stats(self) -> dict:
        with self._lock:
            inflight_age = (
                None
                if self._inflight_since is None
                else round(self._clock() - self._inflight_since, 3)
            )
            calls, filled = self._occ_calls, self._occ_filled
        return self.health.snapshot(
            queue_depth=self.queue_depth,
            inflight_age_s=inflight_age,
            draining=self._draining,
            breaker=self.breaker.state,
            breaker_trips=self.breaker.trips,
            latency_estimates_s=self.estimates.snapshot(),
            buckets=[list(b) for b in self.runner.buckets],
            occupancy={
                "pack": self._pack,
                "batch_size": self.runner.batch_size,
                "device_calls": calls,
                "slots_filled": filled,
                "mean": (
                    round(filled / (calls * self.runner.batch_size), 4)
                    if calls else None
                ),
            },
        )

    # -- planning ----------------------------------------------------------

    def _plan(self, req: InferenceRequest) -> Plan:
        h, w = req.image.shape[:2]
        base = self.runner.pick_bucket(h, w)
        smaller = self.runner.smaller_bucket(base)
        available = [
            lvl for lvl in self.runner.levels()
            if lvl != "small" or smaller is not None
        ]
        remaining = (
            None if req.deadline is None else req.deadline - self._clock()
        )
        full_ok = self.breaker.allow_full()
        level = self.planner.plan(
            remaining, self.estimates.snapshot(), full_ok, available
        )
        if full_ok and level not in FULL_QUALITY_LEVELS:
            # Consumed a half-open probe but was forced to degrade anyway
            # (deadline pressure) — return it, this is not a probe outcome.
            self.breaker.cancel_probe()
        if level == "full":
            return Plan("full", "full", base)
        if level == "small":
            assert smaller is not None
            return Plan("small", "full", smaller)
        if level in ("full_q8", "full_q8n"):
            # q8 programs compile per-bucket like "full" — quantization
            # degrades precision, not resolution.
            return Plan(level, level, base)
        # reduced / proposals programs exist for the smallest bucket only.
        return Plan(level, level, self.runner.buckets[0])

    def _note_pressure(self) -> None:
        if self.health.state == health_mod.READY:
            self.health.transition(health_mod.DEGRADED, "load shedding")

    # -- worker ------------------------------------------------------------

    def _take_batch(self) -> Optional[list[InferenceRequest]]:
        """Next micro-batch: the first live request plus any immediately
        available requests with the SAME plan, up to the static batch."""
        while True:
            if self._carry is not None:
                if self._carry is self._STOP:
                    return []
                first, self._carry = self._carry, None
            else:
                try:
                    first = self._queue.get(timeout=0.1)
                except queue_mod.Empty:
                    return None
            if first is self._STOP:
                return []
            if (
                first.deadline is not None
                and self._clock() > first.deadline
            ):
                self.health.record_deadline_miss()
                self._note_pressure()
                first._set_error(
                    DeadlineExceeded("deadline passed while queued")
                )
                continue
            first.plan = self._plan(first)
            if first.queue_span is not None:
                first.queue_span.end(level=first.plan.level)
            batch = [first]
            while len(batch) < self.runner.batch_size:
                try:
                    nxt = self._queue.get_nowait()
                except queue_mod.Empty:
                    break
                if nxt is self._STOP:
                    # The carry slot is free here (a set carry breaks the
                    # loop above), so park the sentinel: this batch still
                    # runs, the NEXT take returns the stop.
                    self._carry = self._STOP
                    break
                if (
                    nxt.deadline is not None
                    and self._clock() > nxt.deadline
                ):
                    self.health.record_deadline_miss()
                    nxt._set_error(
                        DeadlineExceeded("deadline passed while queued")
                    )
                    continue
                nxt.plan = self._plan(nxt)
                if nxt.queue_span is not None:
                    nxt.queue_span.end(level=nxt.plan.level)
                if nxt.plan[1:] != first.plan[1:]:
                    self._carry = nxt  # different program; runs next
                    break
                batch.append(nxt)
            return batch

    def _expire(self, req: InferenceRequest) -> None:
        """Fail one request whose deadline passed before its device call
        — identical outcome to the unpacked path's queue expiry."""
        self.health.record_deadline_miss()
        self._note_pressure()
        req._set_error(DeadlineExceeded("deadline passed while queued"))

    def _admit_buffered(self, item) -> bool:
        """Plan + buffer one queue item; False when it was the STOP
        sentinel (which parks: the buffer flushes before the stop)."""
        if item is self._STOP:
            self._stop_parked = True
            return False
        if item.deadline is not None and self._clock() > item.deadline:
            self._expire(item)
            return True
        item.plan = self._plan(item)
        if item.queue_span is not None:
            item.queue_span.end(level=item.plan.level)
        self._buf.add(item)
        return True

    def _take_batch_packed(self) -> Optional[list[InferenceRequest]]:
        """Continuous-batching take: pool up to ``2 * batch_size``
        planned requests, then pack the most urgent request's program
        full (serve/batcher.py).  Same contract as :meth:`_take_batch`:
        None = nothing yet, [] = stop, else a same-program batch."""
        bs = self.runner.batch_size
        cap = 2 * bs
        for r in self._buf.expire(self._clock()):
            self._expire(r)
        while not self._stop_parked and len(self._buf) < cap:
            try:
                # Block (the worker's idle wait) only when the buffer is
                # empty; otherwise just sweep what is already queued.
                if len(self._buf):
                    item = self._queue.get_nowait()
                else:
                    item = self._queue.get(timeout=0.1)
            except queue_mod.Empty:
                if not len(self._buf):
                    return None
                break
            if not self._admit_buffered(item):
                break
        if not len(self._buf):
            return [] if self._stop_parked else None
        if (
            self.pack_window_s > 0
            and not self._stop_parked
            and len(self._buf) < bs
        ):
            # Linger for stragglers to top off a partial batch.  Wall
            # clock, not self._clock: tests drive deadlines with fake
            # clocks that never advance on their own.
            t_end = time.monotonic() + self.pack_window_s
            while len(self._buf) < cap:
                left = t_end - time.monotonic()
                if left <= 0:
                    break
                try:
                    item = self._queue.get(timeout=min(left, 0.01))
                except queue_mod.Empty:
                    continue
                if not self._admit_buffered(item):
                    break
        return self._buf.take(bs)

    def _worker_loop(self) -> None:
        while not self._stopping:
            batch = (
                self._take_batch_packed() if self._pack
                else self._take_batch()
            )
            if batch is None:
                continue
            if not batch:  # STOP
                break
            plan = batch[0].plan
            assert plan is not None
            obs.histogram(
                "serve_batch_occupancy",
                "request slots filled / slots total per device call",
                buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
            ).observe(
                len(batch) / self.runner.batch_size,
                level=plan.level, **self._mlabels,
            )
            start = self._clock()
            with self._lock:
                self._occ_calls += 1
                self._occ_filled += len(batch)
                self._inflight_since = start
                self._inflight_plan = plan
                self._inflight_reqs = list(batch)
            dspan = None
            if batch[0].span is not None:
                dspan = batch[0].span.child("device", attrs={
                    "level": plan.level, "bucket": list(plan.bucket),
                    "batch": len(batch),
                })
            try:
                results = self.runner.run(
                    plan.mode, plan.bucket, [r.image for r in batch]
                )
                err: Optional[BaseException] = None
            except BaseException as e:  # noqa: BLE001 - typed below
                results, err = None, e
            finally:
                if dspan is not None:
                    if err is not None:
                        dspan.set(error=type(err).__name__)
                    dspan.end()
                with self._lock:
                    self._inflight_since = None
                    self._inflight_plan = None
                    self._inflight_reqs = []
            if not self.health.alive():
                # The watchdog declared us dead while this call was stuck
                # (its requests were already failed), or a kill() raced
                # this batch between the queue pop and the _inflight_reqs
                # registration — that sweep misses requests this thread
                # held in hand, so fail whatever is still unresolved
                # instead of dropping it to wait out its caller's
                # deadline.  Drop the zombie result either way.
                dead = EngineUnavailable("engine died mid-batch")
                for r in batch:
                    if not r.done():
                        r._set_error(dead)
                self._fail_pending(dead)
                break
            latency = self._clock() - start
            if err is not None:
                self.health.record_failure()
                if plan.level in FULL_QUALITY_LEVELS:
                    self.breaker.record_failure()
                self._note_pressure()
                for r in batch:
                    r._set_error(
                        ServeError(
                            f"inference failed at level {plan.level}: "
                            f"{type(err).__name__}: {err}"
                        )
                    )
                continue
            self.estimates.observe(plan.level, latency)
            late = [
                r for r in batch
                if r.deadline is not None and self._clock() > r.deadline
            ]
            if plan.level in FULL_QUALITY_LEVELS:
                # A full-path overrun that blew the deadline counts against
                # the breaker; an on-time full result heals it.
                if late:
                    self.breaker.record_failure()
                else:
                    self.breaker.record_success()
            for r, res in zip(batch, results):
                # A pack shares one program but not necessarily one
                # LEVEL (full + small ride the same compiled full
                # program): each request reports its own plan's level.
                level = r.plan.level
                if r in late:
                    self.health.record_deadline_miss()
                    self._note_pressure()
                    r._set_error(
                        DeadlineExceeded(
                            f"served at level {level} in "
                            f"{latency:.3f}s, past the deadline"
                        )
                    )
                else:
                    self.health.record_served(level, latency)
                    obs.histogram(
                        "serve_request_latency_seconds",
                        "served request latency (device call to result)",
                    ).observe(latency, level=level,
                              **self._req_labels(r.tenant))
                    res = dict(res)
                    res["level"] = level
                    res["latency_s"] = latency
                    # Fake runners in tests may not tag provenance.
                    res.setdefault(
                        "generation",
                        getattr(self.runner, "generation", 0),
                    )
                    r._set_result(res)
            if (
                self.health.state == health_mod.DEGRADED
                and self.breaker.state == "closed"
                and not late
                and self._queue.qsize() < max(1, self._queue.maxsize // 2)
            ):
                self.health.transition(health_mod.READY, "pressure cleared")

    # -- watchdog ----------------------------------------------------------

    def _fail_pending(self, error: BaseException) -> None:
        for r in self._buf.drain():
            r._set_error(error)
        if self._carry is not None:
            if self._carry is not self._STOP:
                self._carry._set_error(error)
            self._carry = None
        while True:
            try:
                item = self._queue.get_nowait()
            except queue_mod.Empty:
                return
            if item is not self._STOP:
                item._set_error(error)

    def _watchdog_loop(self) -> None:
        while not self._stopping and self.health.alive():
            time.sleep(self.watchdog_poll)
            with self._lock:
                since = self._inflight_since
                plan = self._inflight_plan
            if since is None:
                continue
            age = self._clock() - since
            if age <= self.hang_timeout:
                continue
            self.health.hung += 1
            self.health.transition(
                health_mod.DEAD,
                f"device call hung for {age:.1f}s "
                f"(plan={plan}, hang_timeout={self.hang_timeout}s)",
            )
            obs.emit("serve", "engine_dead", {
                "reason": self.health.reason,
                "queued": self._queue.qsize(),
            }, logger=log)
            obs.flight_dump(
                "engine_dead",
                {"replica": self.replica_id, "reason": self.health.reason},
            )
            error = EngineUnavailable(f"engine died: {self.health.reason}")
            with self._lock:
                stuck = list(self._inflight_reqs)
            for r in stuck:
                # The device call may never return; unblock its waiters.
                r._set_error(error)
            self._fail_pending(error)
            return


def build_engine(
    cfg,
    variables,
    buckets: Optional[Sequence[tuple[int, int]]] = None,
    batch_size: Optional[int] = None,
    int8_head: bool = False,
    int8_network: bool = False,
    device: Optional[object] = None,
    **engine_kwargs,
) -> InferenceEngine:
    """Convenience: real runner + engine from a config and variables
    (checkpoint-restored or freshly initialized).  ``cfg.serve`` supplies
    the micro-batch and packing defaults; explicit arguments win."""
    serve_cfg = getattr(cfg, "serve", None)
    if batch_size is None:
        batch_size = serve_cfg.batch_size if serve_cfg is not None else 1
    if serve_cfg is not None:
        engine_kwargs.setdefault("pack", serve_cfg.pack)
        engine_kwargs.setdefault("pack_window_s", serve_cfg.pack_window_s)
        if "tenancy" not in engine_kwargs:
            engine_kwargs["tenancy"] = tenancy_mod.TenancyPolicy.from_config(
                serve_cfg.tenancy
            )
    runner = DetectorRunner(
        cfg, variables, buckets=buckets, batch_size=batch_size,
        int8_head=int8_head, int8_network=int8_network, device=device,
    )
    return InferenceEngine(runner, **engine_kwargs)
