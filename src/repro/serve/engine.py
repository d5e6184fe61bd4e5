"""Batched serving engine: jit'd prefill + greedy/sampled decode loop.

Production posture:
  * prefill and decode are separate jit'd programs (the two dry-run shapes);
  * KV caches live on device across steps; the host loop only moves tokens;
  * two serving surfaces share the jit'd programs: ``Engine.generate`` runs
    a fixed-size static batch (offline/eval traffic), while
    ``serve.frontend.StreamFrontend`` serves a REQUEST STREAM through the
    per-request step API (``prefill_request`` / ``decode_request`` /
    ``sample_tokens``) with admission control, deadlines, retry/shedding,
    and per-request fault isolation — the robustness substrate the
    slot-recycling continuous-batching scheduler
    (``serve.scheduler.ContinuousScheduler``) plugs into: it moves all
    live requests into ONE batched decode program over a paged KV pool
    (``serve.kv_cache``) while the same step API serves its resume-replay
    and bisection re-run paths;
  * sampling is PER-REQUEST deterministic: each request's sampling key is
    ``fold_in(fold_in(PRNGKey(seed), request_id), step)``, so a request's
    token stream depends only on (params, prompt, request_id) — retries,
    evictions, or shedding of batch neighbors never change another
    request's tokens (the front-end's bitwise fault-isolation property);
  * with ``ServeConfig.pack_weights=True`` every dense weight (attention,
    MLP, SSM projections AND the LM head) is tile-major packed ONCE at
    engine construction (``models.layers.pack_model_params``), and MoE
    expert stacks are grouped-packed per expert (GroupedPackedWeight). Each
    prefill/decode step then runs the pack-free-A fused GEMM kernels: no
    per-call packing, bias/activation applied in the kernel's store
    epilogue, and the MoE gate/up pair fused into one grouped silu-gate
    kernel pass (see core/layered.py);
  * packed MoE serving is RAGGED: all three expert contractions (the fused
    gate/up pass and the down-projection) run through the scalar-prefetch
    grid of ``gemm_grouped_packed_ragged``, fed by the per-(group, expert)
    occupied-slot counts the router computes for free. Counts contract:
    ``counts[g, e] <= C`` (the padded capacity), dtype int32, passed as the
    kernel's scalar-prefetch operand — valid rows are a prefix of each
    expert's capacity segment, all-padding (expert, m-block) grid steps
    early-out the K-loop, and the partial block is clamped with an iota
    mask. A skewed decode/prefill router therefore pays for the tokens it
    actually routed, not for ``capacity_factor`` times that;
  * serving contractions are GUARDED: env/auto dispatch degrades a failing
    lowering to the next-cheapest supporting one (bottoming out at the jnp
    reference path), recording every degradation in the dispatch-health
    registry — a degraded deployment keeps serving AND says so through
    ``Engine.health_report()`` instead of crashing or silently slowing.
  * ``ServeConfig.quantize`` (requires ``pack_weights=True``) quantizes
    every packed weight at load — dense projections, the LM head, and all
    three MoE expert stacks. ``"int8"``: int8 tiles + per-(Kb,Nb)-tile f32
    scales (weight traffic halves vs bf16). ``"int4"``: nibble-packed tiles
    — two values per byte, widened to i8 in-kernel by shift/mask, so B's
    HBM→VMEM traffic is 0.25x bf16. A ``":col"`` suffix on either
    ("int8:col" / "int4:col") switches to ONE f32 scale per Nb column.
    Scale contract: the [Nb, Kb] (grouped: [E, Nb, Kb]) tile-granularity
    scale grid rides next to each packed buffer in the params tree, sits
    whole in the kernel's SMEM (read at the tile coordinates B's index map
    fetched, live steps only on the ragged path), and dequantizes each K-step's
    partial product on the VMEM f32 accumulator BEFORE
    bias/activation/silu-gate; a col-granularity [Nb] ([E, Nb]) scale is
    K-invariant, hoists out of the K loop entirely, and multiplies the
    finished accumulator ONCE in the store epilogue (store-only dequant) —
    still ahead of bias/activation/gate, so every fused epilogue and the
    ragged counts path run quantized unchanged.
  * the continuous-batching scheduler's paged KV pool quantizes
    independently via ``ContinuousConfig.kv_quantize="int8"`` (int8 blocks
    + per-position f32 scales; see ``serve.kv_cache``) — roughly 2x
    concurrent resident tokens per KV byte budget, with the preempt/resume
    and bisection contracts intact.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ContractionSpec, EPILOGUE_SPECS, dispatch, is_packed
from repro.core import health
from repro.models import Model
from repro.models.layers import pack_model_params


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0      # 0 => greedy
    cache_dtype: str = "float32"
    seed: int = 0
    pack_weights: bool = False    # load-time tile-major packing of all
                                  # dense weights (serving fast path)
    quantize: str | None = None   # "int8" | "int4" (+":col"): quantize
                                  # packed weights at load (dequant-in-
                                  # epilogue narrow-HBM serving; int4 packs
                                  # two nibbles/byte; ":col" = store-only
                                  # per-column scales; needs
                                  # pack_weights=True)


def _find_moe_subtree(tree):
    if not isinstance(tree, dict):
        return None
    if isinstance(tree.get("moe"), dict):
        return tree["moe"]
    for v in tree.values():
        found = _find_moe_subtree(v)
        if found is not None:
            return found
    return None


def serving_dispatch_report(model_cfg, cfg: "ServeConfig",
                            params) -> Dict[str, str]:
    """Declare the serving step's canonical contractions as ContractionSpecs
    and record which registered lowering ``dispatch`` chooses for each.

    The declarative surface makes the serving plan inspectable before the
    first token: the report keys are stable spec descriptions (LM head at
    prefill/decode shapes; the MoE gate/up chain and down-projection when
    the model has expert stacks), the values the chosen lowering names.
    Representative shapes: prefill = one ``max_len`` sequence, decode = one
    token; grouped specs use the routing group's capacity envelope with the
    balanced-router occupancy prior ``1/capacity_factor``.
    """
    compute = model_cfg.compute_dtype
    d, v = model_cfg.d_model, model_cfg.vocab_size
    head = params.get("head_packed")
    report = {}
    for phase, m in (("prefill", cfg.max_len), ("decode", 1)):
        spec = ContractionSpec.dense(m, d, v, compute, w=head, accum="f32")
        report[f"lm_head.{phase}:{spec.describe()}"] = dispatch(spec).name
    moe = _find_moe_subtree(params)
    if moe is not None and getattr(model_cfg, "num_experts", 0) > 1:
        from repro.models.moe import GROUP_SIZE, _capacity
        e = model_cfg.num_experts
        capacity = _capacity(min(GROUP_SIZE, cfg.max_len), model_cfg)
        occ = min(1.0, 1.0 / model_cfg.capacity_factor)
        wg, wo = moe["wg"], moe["wo"]
        ragged = is_packed(wg)  # packed serving threads the routing counts
        f = wg.n if is_packed(wg) else wg.shape[-1]
        gate = ContractionSpec.grouped(
            e, capacity, d, f, compute, w=wg,
            epilogue=EPILOGUE_SPECS["silu_gate"], counts=ragged,
            occupancy=occ)
        down = ContractionSpec.grouped(
            e, capacity, f, d, compute, w=wo, counts=ragged, occupancy=occ)
        report[f"moe.gate_up:{gate.describe()}"] = dispatch(gate).name
        report[f"moe.down:{down.describe()}"] = dispatch(down).name
    return report


class Engine:
    def __init__(self, model: Model, params, cfg: ServeConfig = ServeConfig()):
        self.model = model
        if cfg.quantize and not cfg.pack_weights:
            raise ValueError("ServeConfig.quantize requires pack_weights=True "
                             "(quantization lives in the packed-tile format)")
        if cfg.pack_weights:
            params = pack_model_params(model.cfg, params,
                                       quantize=cfg.quantize)
        self.params = params
        self.cfg = cfg
        # The serving plan, declared: spec -> chosen lowering per canonical
        # serving contraction (observability; see serving_dispatch_report).
        self.dispatch_report = serving_dispatch_report(model.cfg, cfg, params)
        self._prefill = jax.jit(
            lambda p, batch: model.prefill(
                p, batch, max_len=cfg.max_len,
                cache_dtype=jnp.dtype(cfg.cache_dtype)))
        self._decode = jax.jit(model.decode)
        # Jitted samplers (one compile per logits batch width, cached for
        # the process): the eager vmap re-traces every call, which dominates
        # the serving step at small batch sizes.
        base, temp = jax.random.PRNGKey(cfg.seed), cfg.temperature

        @jax.named_scope("sample")
        def _sampled(logits, rids, steps):
            def one(rid, s, row):
                key = jax.random.fold_in(jax.random.fold_in(base, rid), s)
                return jax.random.categorical(key, row / temp, axis=-1)
            return jax.vmap(one)(rids, steps, logits).astype(jnp.int32)

        self._sampled = jax.jit(_sampled)
        self._argmax = jax.jit(jax.named_scope("sample")(
            lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32)))

    def health_report(self) -> Dict[str, dict]:
        """The dispatch-health registry's degradation report.

        Empty dict == healthy: every contraction ran on its dispatch
        winner. A non-empty report means the guarded runner degraded at
        least one ``(spec, lowering)`` — each entry records the failure
        count, classified cause (compile / resource / unsupported /
        numerics / runtime), the fallback lowering that took over, and the
        last failure's detail string. Degradations are decided when a
        contraction traces/executes, so check AFTER traffic (the first
        ``generate`` call bakes prefill/decode decisions in at jit trace
        time). The registry is process-global (``repro.core.health``):
        engines sharing a process share the report.
        """
        return health.health_report()

    def serve_report(self) -> Dict[str, dict]:
        """The request-lifecycle report of the stream front-end.

        ``counters`` are the monotonic conservation counters (offered =
        admitted + shed; every admitted request ends exactly once as
        completed / evicted / deadline_miss), ``requests`` the retained
        per-request lifecycle records (bounded ring; ``dropped_records``
        counts evictions from the ring, never from the counters), and
        ``dispatch_health`` the dispatch registry's bound stats. Like
        ``health_report`` the registry is process-global
        (``repro.core.health.SERVE``): engines sharing a process share it.
        """
        return health.serve_report()

    def sample_tokens(self, logits: jnp.ndarray, request_ids,
                      step) -> jnp.ndarray:
        """Sample one token per row with PER-REQUEST keys.

        ``logits``: [B, V]; ``request_ids``: [B] int; ``step``: the
        request-local sampling index (0 == the token sampled from prefill
        logits) — a scalar, or a [B] vector when rows sit at DIFFERENT
        steps (the continuous-batching scheduler's shared batch mixes
        requests at unrelated stream offsets). Key derivation is
        ``fold_in(fold_in(PRNGKey(seed), request_id), step)`` per row — no
        state is threaded between steps or across rows, so retrying a step
        resamples the SAME token and neighbors' lifecycles (or batch
        composition) can't perturb a request's stream. Greedy
        (temperature<=0) ignores the keys.
        """
        if self.cfg.temperature <= 0.0:
            return self._argmax(logits)
        rids = jnp.asarray(request_ids, jnp.int32)
        steps = jnp.broadcast_to(jnp.asarray(step, jnp.int32), rids.shape)
        return self._sampled(logits, rids, steps)

    # ----- per-request step API (the stream front-end's substrate) --------

    def prefill_request(self, tokens) -> tuple:
        """Prefill ONE request's prompt ([S] int32) in its own batch-1 slot.
        Returns (last-position logits [1, V], decode caches for the slot)."""
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None]}
        return self._prefill(self.params, batch)

    def decode_request(self, caches, token, pos: int) -> tuple:
        """One decode step for one request's slot: ``token`` [1,1] int32 at
        absolute position ``pos``. Pure in (caches, token, pos) — a failed
        step can be retried with identical inputs and identical result."""
        pos_v = jnp.full((1,), pos, jnp.int32)
        return self._decode(self.params, caches, token, pos_v)

    def generate(self, batch: dict, max_new_tokens: int,
                 prompt_len: Optional[int] = None,
                 request_ids=None) -> np.ndarray:
        """batch: model-format prompt batch; returns [B, max_new_tokens].

        ``request_ids`` ([B] ints, default ``arange(B)``) seed each row's
        sampling key stream (see ``sample_tokens``).
        """
        tokens = batch["tokens"]
        b, t = tokens.shape
        prompt_len = prompt_len or t
        prefix = (self.model.cfg.num_patches
                  if self.model.cfg.family == "vlm" else 0)
        rids = (jnp.arange(b, dtype=jnp.int32) if request_ids is None
                else jnp.asarray(request_ids, jnp.int32))
        last_logits, caches = self._prefill(self.params, batch)
        out = []
        tok = self.sample_tokens(last_logits, rids, step=0)[:, None]
        for i in range(max_new_tokens):
            out.append(np.asarray(tok))
            pos = jnp.full((b,), prefix + prompt_len + i, jnp.int32)
            logits, caches = self._decode(self.params, caches, tok, pos)
            tok = self.sample_tokens(logits[:, 0], rids, step=i + 1)[:, None]
        return np.concatenate(out, axis=1)
