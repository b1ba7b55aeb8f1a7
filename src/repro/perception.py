"""Stock batched perception step: wire/bag payloads -> decode -> model
forward under ONE jit, with donated batch buffers.

The paper's "User Logic" for playback simulation is a perception model
consuming decoded sensor records.  Before this module the platform ran
that as two worlds glued by Python: the Pallas decode produced features,
control returned to the host, and any model forward was a separate
dispatch with fresh buffers.  :class:`PerceptionStep` fuses the whole
consumer into one compiled program:

    payload (R, Nb) u8 --sensor_decode[_metrics]--> features (R, Nb) f32
        --reshape--> embeds (R, Nb/d_model, d_model)
        --model forward (transformer.py / ssm.py archs)--> logits
        --last position, first ``out_features`` lanes--> (R, out_features)

``jax.jit(..., donate_argnums=...)`` donates the batch buffers (payload /
scale / zero_point / lengths [/ ts_low]), so the steady-state replay loop
re-uses the previous batch's device allocations instead of growing the
arena each step — together with the zero-copy ``frame_to_batch`` feed
(:func:`repro.net.wire.frame_to_batch`) the path from a received DATA
frame to model logits performs no per-message work at all.

Scenario integration: ``user_logic="perception://<model>"`` resolves (via
``resolve_logic_ref``) to a cached :class:`PerceptionStep` and runs it as
a first-class *batched* logic — no custom callables.  ``<model>`` is any
registered arch name (``qwen3-4b``, ``falcon-mamba-7b``, ...), built at its
published widths, or that name with a ``-tiny`` suffix (``qwen3-4b-tiny``)
for its reduced same-structure config (:func:`repro.configs.tiny
.tiny_config`), which keeps CPU suites cheap.  Params are initialised on
the device under one jit, in the config's dtype, and are deterministic in
``seed``, so two steps built from the same ref are bit-identical — golden
verdicts are stable across runs and processes.

Thread backends only: the step owns jitted state, and process-backend
workers fork from a jax-loaded driver (initialising jax there can
deadlock) — ``ScenarioSuite`` rejects the combination up front.
"""

from __future__ import annotations

import warnings
from contextlib import contextmanager
from typing import Optional, Sequence

import numpy as np

from repro.core.bag import Message
from repro.kernels.compat import resolve_interpret
from repro.obs import trace as otrace
from repro.obs.compiles import watch_compiles

#: default topic perception outputs publish on
OUT_TOPIC = "/perception"

#: model-name suffix selecting an arch's reduced same-structure config
TINY_SUFFIX = "-tiny"


def _ts_low(timestamps: np.ndarray) -> np.ndarray:
    return (np.asarray(timestamps).astype(np.uint64)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def resolve_config(model: str):
    """The config a model name names: ``<arch>-tiny`` is the reduced
    same-structure config of ``<arch>``, any other name the registered
    config at its published widths."""
    from repro.configs.tiny import tiny_config
    from repro.models import get_config
    if model.endswith(TINY_SUFFIX):
        return tiny_config(model[:-len(TINY_SUFFIX)])
    return get_config(model)


def init_params(cfg, seed: int):
    """Deterministic params for ``cfg``, made on the default device under
    one jit in the config's dtype, so no float32 copy of a bf16 model is
    ever materialised on the host."""
    import jax
    from repro.models import get_model
    return jax.jit(get_model(cfg).init_params)(jax.random.PRNGKey(seed))


def features_to_logits(cfg, params, feats, out_features: int):
    """Model head over decoded features: (R, Nb) f32 -> (R, out_features)
    f32.  Rows are cut into ``Nb // d_model`` embedding tokens, run through
    the model forward, and the last position's first ``out_features``
    logits are kept.  Also the reference face: the same forward over
    features decoded off the Pallas path."""
    import jax.numpy as jnp
    from repro.models import get_model
    d_model = cfg.d_model
    R, Nb = feats.shape
    S = Nb // d_model
    if S == 0:
        raise ValueError(
            f"payload rows of {Nb} bytes are narrower than "
            f"d_model={d_model}; pad records to at least one token")
    embeds = feats[:, :S * d_model].reshape(R, S, d_model)
    logits = get_model(cfg).forward(params, {"embeds": embeds})
    return logits[:, -1, :out_features].astype(jnp.float32)


def build_step(cfg, *, out_features: int, metrics: bool, donate: bool,
               interpret: bool):
    """The jitted decode→forward program a :class:`PerceptionStep` runs:
    ``step(params, payload, scale, zero_point, lengths[, ts_low]) ->
    (logits, record_digests | None)``.  Module-level so it can be lowered
    against ``jax.eval_shape`` params without initialising a model."""
    import jax
    from repro.kernels.sensor_decode import (sensor_decode,
                                            sensor_decode_metrics)

    if metrics:
        def step(params, payload, scale, zero_point, lengths, ts_low):
            out = sensor_decode_metrics(payload, scale, zero_point,
                                        lengths, ts_low,
                                        interpret=interpret)
            return (features_to_logits(cfg, params, out["features"],
                                       out_features),
                    out["record_digests"])
        donate_argnums = (1, 2, 3, 4, 5)
    else:
        def step(params, payload, scale, zero_point, lengths):
            feats = sensor_decode(payload, scale, zero_point, lengths,
                                  interpret=interpret)
            return features_to_logits(cfg, params, feats, out_features), None
        donate_argnums = (1, 2, 3, 4)
    # params (arg 0) are NOT donated — they persist across steps; the
    # batch buffers are consumed exactly once, which is what makes
    # them donatable
    return jax.jit(step, donate_argnums=donate_argnums if donate else ())


@contextmanager
def _donation_quiet():
    """The logits output is smaller than the donated payload buffer, so
    backends that only alias shape-matched pairs report the donation as
    "not usable" — the early-free half of donation still applies, and the
    warning would fire once per trace."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class PerceptionStep:
    """Jitted decode→forward consumer with a donated steady-state loop.

    ``model`` — registered arch name at its published widths, or
    ``<arch>-tiny`` for the reduced config (see :func:`resolve_config`;
    attention archs exercise ``models/transformer.py``, SSM archs
    ``models/ssm.py`` through the same forward).  ``metrics=True`` swaps
    the decode for the fused ``sensor_decode_metrics`` sweep, so the step
    also returns per-record input digests (the aggregation checksums) for
    free.  ``interpret`` resolves once at construction via
    :func:`repro.kernels.compat.resolve_interpret` — env
    ``REPRO_PALLAS_INTERPRET``, else compiled on TPU.  ``donate=False``
    opts out of buffer donation (keeps inputs readable after the call —
    for tests and debugging).  ``seed=None`` makes no weights: the step
    can then be compiled (:meth:`hlo_text`) but not run.

    Callable as the batched user-logic contract
    (``list[Message] -> [(topic, ts, bytes)]``); :meth:`run_batch` is the
    zero-copy face (columnar batch dict in, columnar batch dict out).
    """

    def __init__(self, model: str, seed: Optional[int] = 0,
                 out_topic: str = OUT_TOPIC, out_features: int = 16,
                 metrics: bool = False, donate: bool = True,
                 interpret: Optional[bool] = None):
        cfg = resolve_config(model)
        if out_features < 1 or out_features > cfg.vocab_size:
            raise ValueError(f"out_features must be in [1, {cfg.vocab_size}]")
        self.model = model
        self.seed = seed
        self.out_topic = out_topic
        self.out_features = out_features
        self.metrics = metrics
        self.donate = donate
        self.interpret = resolve_interpret(interpret)
        self.cfg = cfg
        watch_compiles()
        self.params = None if seed is None else init_params(cfg, seed)
        self._step = build_step(cfg, out_features=out_features,
                                metrics=metrics, donate=donate,
                                interpret=self.interpret)

    def hlo_text(self, rows: int, row_bytes: int) -> str:
        """Optimized HLO text of the program this step runs on batches of
        ``rows`` records of ``row_bytes`` bytes, compiled from shapes alone
        (a cache hit where the process or the persistent compilation cache
        holds that shape).  Each instruction's ``op_name`` carries the
        model's named scopes (``attention``, ``mlp``, ``head``)."""
        import jax
        import jax.numpy as jnp
        params = jax.eval_shape(lambda: init_params(self.cfg, 0))
        dtypes = [jnp.float32, jnp.float32, jnp.int32]
        if self.metrics:
            dtypes.append(jnp.uint32)
        args = [jax.ShapeDtypeStruct((rows, row_bytes), jnp.uint8)] + [
            jax.ShapeDtypeStruct((rows,), d) for d in dtypes]
        with _donation_quiet():
            return self._step.lower(params, *args).compile().as_text()

    # -- array faces --------------------------------------------------------

    def step_arrays(self, batch: dict):
        """Run the fused step over one columnar batch.

        Returns ``(logits, record_digests)``: (R, out_features) f32 device
        array, plus (R,) uint32 input digests when ``metrics=True`` (else
        ``None``).  The batch buffers are copied to fresh device arrays
        and those — not the caller's numpy memory — are donated, so a
        zero-copy frame view stays valid after the call.
        """
        import jax.numpy as jnp
        tr = otrace.TRACER
        slot = (tr.begin("perception.step", "logic",
                         attrs={"rows": len(batch["lengths"]),
                                "row_bytes": batch["payload"].shape[1]})
                if tr is not None else None)
        args = [jnp.array(batch["payload"]), jnp.array(batch["scale"]),
                jnp.array(batch["zero_point"]),
                jnp.array(np.asarray(batch["lengths"], dtype=np.int32))]
        if self.metrics:
            args.append(jnp.array(_ts_low(batch["timestamps"])))
        with _donation_quiet():
            out = self._step(self.params, *args)
        if slot is not None:
            otrace.Tracer.end(slot)
        return out

    def run_batch(self, batch: dict) -> dict:
        """Zero-copy face: columnar batch in, columnar output batch out.

        The output dict has the same shape contract the input had —
        ``payload`` is the (R, 4*out_features) uint8 view of the f32
        logits rows, ``timestamps`` pass through, and the routing columns
        name ``out_topic`` — so it feeds :func:`batch_to_frame` for
        republish, or :func:`accumulate_topic_state_arrays` for metrics,
        without ever materialising ``Message`` objects.
        """
        logits, digests = self.step_arrays(batch)
        tr = otrace.TRACER
        slot = (tr.begin("perception.readback", "logic",
                         attrs={"rows": logits.shape[0]})
                if tr is not None else None)
        out = np.asarray(logits)
        if digests is not None:
            digests = np.asarray(digests)
        if slot is not None:
            otrace.Tracer.end(slot)
        payload = np.ascontiguousarray(out).view(np.uint8).reshape(
            out.shape[0], out.shape[1] * 4)
        result = {
            "payload": payload,
            "lengths": np.full(out.shape[0], payload.shape[1],
                               dtype=np.int32),
            "timestamps": np.asarray(batch["timestamps"], dtype=np.int64),
            "scale": np.full(out.shape[0], 1.0 / 255.0, dtype=np.float32),
            "zero_point": np.zeros(out.shape[0], dtype=np.float32),
            "topics": (self.out_topic,),
            "topic_idx": np.zeros(out.shape[0], dtype=np.uint32),
        }
        if digests is not None:
            result["input_record_digests"] = digests
        return result

    # -- batched user-logic contract -----------------------------------------

    def __call__(self, msgs: Sequence[Message]):
        from repro.data.pipeline import assemble_message_batch
        batch = assemble_message_batch(msgs)
        logits, _ = self.step_arrays(batch)
        tr = otrace.TRACER
        slot = (tr.begin("perception.readback", "logic",
                         attrs={"rows": len(msgs)})
                if tr is not None else None)
        out = np.asarray(logits)
        if slot is not None:
            otrace.Tracer.end(slot)
        return [(self.out_topic, m.timestamp, out[i].tobytes())
                for i, m in enumerate(msgs)]


_STEPS: dict[str, PerceptionStep] = {}

SCHEME = "perception://"


def _model_of(ref: str) -> str:
    return ref[len(SCHEME):] if ref.startswith(SCHEME) else ref


def get_step(ref: str) -> PerceptionStep:
    """Resolve (and cache per process) the step a ``perception://<model>``
    logic ref names.  The cache keeps the jit trace warm across the
    partitions/scenarios of a suite — every partition of every scenario
    naming the same model shares one compiled program and one param set."""
    model = _model_of(ref)
    step = _STEPS.get(model)
    if step is None:
        step = _STEPS[model] = PerceptionStep(model=model)
    return step


def step_hlo(ref: str, rows: int, row_bytes: int) -> str:
    """:meth:`PerceptionStep.hlo_text` of the step :func:`get_step`
    resolves ``ref`` to, built with the same settings but no weights."""
    return PerceptionStep(model=_model_of(ref), seed=None).hlo_text(
        rows, row_bytes)


__all__ = ["OUT_TOPIC", "PerceptionStep", "SCHEME", "build_step",
           "features_to_logits", "get_step", "init_params", "resolve_config",
           "step_hlo"]
