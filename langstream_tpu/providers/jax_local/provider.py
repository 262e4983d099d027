"""The ``jax-local`` ServiceProvider: completions + embeddings on the TPU.

Owns ``resources:`` entries of type ``jax-local``. Example:

.. code-block:: yaml

    configuration:
      resources:
        - type: "jax-local"
          name: "tpu-llm"
          configuration:
            model:
              preset: "llama-3-8b"        # or explicit dims
            checkpoint: "/models/llama-3-8b"   # HF dir; omit = random init
            tokenizer: {type: "hf", path: "/models/llama-3-8b"}
            mesh: {tp: 8}                  # jax.sharding axes
            engine: {max-slots: 16, max-seq-len: 4096}
            embeddings-model:
              preset: "minilm-l6"
              checkpoint: "/models/all-MiniLM-L6-v2"

One engine (and one embedder) is built per resource entry and shared by
every agent in the process (the runner loop batches into it). This is the
in-process replacement for the reference's HTTPS providers — the
ServiceProvider SPI surface is identical
(``services/ServiceProvider.java:24``).
"""

from __future__ import annotations

import asyncio
import logging
import uuid
from typing import Any, Dict, List, Optional

from langstream_tpu.api.service import (
    ChatChunk,
    ChatCompletionResult,
    ChatMessage,
    CompletionsService,
    EmbeddingsService,
    ServiceProvider,
    StreamingChunksConsumer,
)
from langstream_tpu.parallel.mesh import MeshConfig

logger = logging.getLogger(__name__)


class JaxCompletionsService(CompletionsService):
    def __init__(self, config: Dict[str, Any]) -> None:
        from langstream_tpu.providers.jax_local import model as model_lib
        from langstream_tpu.providers.jax_local.engine import DecodeEngine
        from langstream_tpu.providers.jax_local.tokenizer import get_tokenizer

        import os

        model_config = model_lib.LlamaConfig.from_dict(config.get("model", {"preset": "tiny"}))
        checkpoint = config.get("checkpoint")
        if checkpoint and any(
            f.endswith(".safetensors") or f == "model.safetensors.index.json"
            for f in (
                os.listdir(checkpoint) if os.path.isdir(checkpoint) else []
            )
        ):
            # direct safetensors load: one fp32 tensor transient at a time
            from langstream_tpu.providers.jax_local.weights import (
                load_safetensors_checkpoint,
            )

            model_config, params = load_safetensors_checkpoint(checkpoint)
            logger.info(
                "loaded safetensors %s (%d params)",
                checkpoint, model_config.num_params(),
            )
        elif checkpoint and os.path.isdir(checkpoint) and any(
            entry.isdigit() and os.path.isdir(os.path.join(checkpoint, entry))
            for entry in os.listdir(checkpoint)
        ):
            # orbax checkpoint (save_model export or Trainer save dir —
            # numeric step subdirs); load_model restores the latest step
            from langstream_tpu.training.checkpoint import load_model

            model_config, params = load_model(checkpoint)
            logger.info(
                "loaded orbax checkpoint %s (%d params)",
                checkpoint, model_config.num_params(),
            )
        elif checkpoint:
            model_config, params = model_lib.load_hf_checkpoint(checkpoint)
            logger.info("loaded checkpoint %s (%d params)", checkpoint, model_config.num_params())
        elif config.get("quantization") == "int8":
            # random weights + int8: init directly in int8 on device — an
            # 8B model inits in ~9 GB instead of peaking at 24 GB
            from langstream_tpu.providers.jax_local.quant import (
                init_quantized_params_cached,
            )

            params = init_quantized_params_cached(
                model_config, seed=int(config.get("seed", 0))
            )
            logger.warning(
                "jax-local: no checkpoint configured — RANDOM int8 weights "
                "(%.2fB params, benchmarking only)",
                model_config.num_params() / 1e9,
            )
        else:
            params = model_lib.init_params(model_config, seed=int(config.get("seed", 0)))
            logger.warning(
                "jax-local: no checkpoint configured — RANDOM weights "
                "(%.2fB params, benchmarking only)", model_config.num_params() / 1e9
            )
        self.tokenizer = get_tokenizer(config.get("tokenizer"))
        engine_config = config.get("engine", {}) or {}
        mesh_config = (
            MeshConfig.from_config(config.get("mesh")) if config.get("mesh") else None
        )
        buckets = engine_config.get("prefill-buckets")
        if isinstance(buckets, str):
            # allow "128" / "128,256" spellings from globals
            buckets = [
                int(b) for b in buckets.replace(",", " ").split()
            ] or None
        elif isinstance(buckets, int):
            buckets = [buckets]
        elif buckets:
            buckets = [int(b) for b in buckets]
        else:
            buckets = None
        if engine_config.get("sampling-seed") is not None:
            sampling_seed = int(engine_config["sampling-seed"])
        else:
            # real entropy by default: without it, every restart/replica
            # would hand unseeded requests the SAME auto-seed sequence,
            # making "random" sampling repeat across processes. Tests
            # constructing DecodeEngine directly keep the deterministic
            # seed=0 default.
            import secrets as _secrets

            sampling_seed = _secrets.randbits(32)
        engine_kwargs = dict(
            mesh_config=mesh_config,
            max_slots=int(engine_config.get("max-slots", 8)),
            # coerce like every other engine knob: placeholder defaults
            # (`${globals.x:-4096}`) arrive as STRINGS
            max_seq_len=(
                int(engine_config["max-seq-len"])
                if engine_config.get("max-seq-len") is not None
                else None
            ),
            prefill_buckets=buckets,
            decode_chunk=int(engine_config.get("decode-chunk", 8)),
            seed=sampling_seed,
            quantize=config.get("quantization"),
            kv_quant=engine_config.get("kv-quant") or None,
            # paged KV cache + persistent prefix-block pool (dense stays
            # the default); placeholder defaults arrive as STRINGS like
            # every other engine knob
            kv_layout=str(
                engine_config.get("kv-layout") or "dense"
            ).lower(),
            kv_block_size=int(engine_config.get("kv-block-size") or 16),
            kv_blocks=(
                int(engine_config["kv-blocks"])
                if engine_config.get("kv-blocks")
                else None
            ),
            # host-DRAM demotion tier capacity (0 = HBM-only pool):
            # evicted chains demote to a pinned host arena and promote
            # back on a digest hit instead of recomputing
            kv_host_blocks=int(engine_config.get("kv-host-blocks") or 0),
            # paged attention kernel: fused ragged Pallas launch over
            # the block tables (default) vs the gather/scatter reference
            # oracle — the ROADMAP-item-1 A/B knob
            paged_kernel=str(
                engine_config.get("paged-kernel") or "fused"
            ).lower(),
            # speculative decoding (ROADMAP item 2): off (oracle scan,
            # default) | ngram (self-drafting prompt-lookup, spec-k
            # drafts verified per step) — threaded exactly like
            # paged-kernel so serve/bench/globals all speak one knob
            spec_decode=str(
                engine_config.get("spec-decode") or "off"
            ).lower(),
            spec_k=int(engine_config.get("spec-k") or 4),
            spec_ngram=int(engine_config.get("spec-ngram") or 2),
            # mixed prefill+decode dispatch (paged only): chunked
            # prefill windows fused into the decode step — the
            # tail-TPOT A/B knob, threaded exactly like paged-kernel
            prefill_mode=str(
                engine_config.get("prefill-mode") or "split"
            ).lower(),
            prefill_chunk=int(engine_config.get("prefill-chunk") or 64),
            # mixed-step carry: pipeline consecutive mixed steps off the
            # previous step's device-resident outputs (on by default —
            # bitwise-neutral; the A/B knob isolates its contribution)
            mixed_carry=str(
                engine_config.get("mixed-carry", "on")
            ).lower() not in ("0", "false", "no", "off"),
            pipeline_decode=str(
                engine_config.get("pipeline-decode", "")
            ).lower() in ("1", "true", "yes"),
            prefix_cache=str(
                engine_config.get("prefix-cache", "true")
            ).lower() not in ("0", "false", "no"),
            # OpenAI `top_logprobs`: static K per engine (shapes the jit
            # outputs); requests may ask for any n <= K
            logprobs_topk=int(engine_config.get("logprobs-top-k", 0) or 0),
            # SLO targets (`slo: {ttft-ms-p95: 200, tpot-ms-p95: 30}`):
            # feed the multi-window burn-rate gauges on every /metrics
            # surface and the `top` SLO panel
            slo=(
                {
                    str(k).replace("-", "_"): float(v)
                    for k, v in (config.get("slo") or {}).items()
                    if v
                }
                or None
            ),
            # admission deadline (serve --queue-timeout-s): pending
            # requests older than this shed with a typed 503 instead of
            # starving in the engine queue
            queue_timeout_s=(
                float(engine_config["queue-timeout-s"])
                if engine_config.get("queue-timeout-s")
                else None
            ),
        )
        precompile = str(engine_config.get("precompile", "")).lower() in (
            "1", "true", "yes",
        )

        def build_engine() -> DecodeEngine:
            # the supervisor's rebuild path runs this exact closure:
            # config + ALREADY-LOADED weights are captured, so healing
            # never reloads a checkpoint, and precompiled variants come
            # back through the persistent XLA compile cache
            nonlocal params
            engine = DecodeEngine(model_config, params, **engine_kwargs)
            # keep the engine's PLACED (quantized, sharded) weights for a
            # rebuild, not the loader's: under tp>1 those sit whole on
            # device 0, a second copy of the model next to its shard
            params = engine.params
            if precompile:
                # compile every prefill/decode variant before the first
                # request so no jit compile ever stalls live traffic
                engine.precompile()
            return engine

        # decode-stall watchdog: opt-in (`serve` turns it on; pods via
        # engine config or LANGSTREAM_WATCHDOG=1) — a degraded/wedged
        # engine flushes flight evidence and bumps watchdog_trips_total
        # instead of waiting for a human to notice
        self.watchdog = None
        watchdog_flag = str(
            engine_config.get(
                "watchdog", os.environ.get("LANGSTREAM_WATCHDOG", "")
            )
        ).lower()
        watchdog_on = watchdog_flag in ("1", "true", "yes", "on")

        def build_watchdog(engine: DecodeEngine):
            from langstream_tpu.runtime.watchdog import EngineWatchdog

            return EngineWatchdog(engine)

        # engine supervisor (self-healing serving): on by default — a
        # crashed device thread snapshots every live session, rebuilds
        # the engine, and resumes each stream bitwise instead of mass-
        # 500ing. Opt out via engine config `supervisor: false`,
        # LANGSTREAM_SUPERVISOR=0, or `serve --no-supervisor` (the
        # multi-host mirror path disables it — a rebuilt leader cannot
        # resynchronize followers yet).
        self._supervisor = None
        self._engine: Optional[DecodeEngine] = None
        supervised = str(
            engine_config.get(
                "supervisor", os.environ.get("LANGSTREAM_SUPERVISOR", "1")
            )
        ).lower() not in ("0", "false", "no", "off")
        if supervised:
            from langstream_tpu.runtime.supervisor import EngineSupervisor

            self._supervisor = EngineSupervisor(
                build_engine,
                max_restarts=int(engine_config.get("max-restarts") or 3),
                restart_window_s=float(
                    engine_config.get("restart-window-s") or 600.0
                ),
                watchdog_factory=build_watchdog if watchdog_on else None,
            )
            self.watchdog = self._supervisor.watchdog
        else:
            self._engine = build_engine()
            self._engine.start()
            if watchdog_on:
                self.watchdog = build_watchdog(self._engine)
                self.watchdog.start()
        self.top_logprobs_limit = self.engine.logprobs_topk

    @property
    def engine(self):
        """The CURRENT engine: the supervisor swaps it on a rebuild, so
        everything downstream (metrics callbacks, the serve wiring, the
        mirror hookup) must read through this property rather than
        caching the instance."""
        if self._supervisor is not None:
            return self._supervisor.engine
        return self._engine

    def available(self) -> Optional[float]:
        """None when accepting work; otherwise the seconds a caller
        should wait (degraded mode: the supervisor is rebuilding a
        crashed engine). The OpenAI surface turns this into
        503 + Retry-After before burning any tokenization work."""
        supervisor = self._supervisor
        if supervisor is not None and supervisor.state == "rebuilding":
            # (a supervisor past its restart budget is "failed", which
            # is terminal — those requests should 500, not retry)
            return supervisor.retry_after()
        return None

    async def get_chat_completions(
        self,
        messages: List[ChatMessage],
        options: Dict[str, Any],
        stream_consumer: Optional[StreamingChunksConsumer] = None,
    ) -> ChatCompletionResult:
        prompt_tokens = self.tokenizer.apply_chat_template(
            [{"role": m.role, "content": m.content} for m in messages]
        )
        return await self._generate(prompt_tokens, options, stream_consumer)

    async def get_text_completions(
        self,
        prompt: List[str],
        options: Dict[str, Any],
        stream_consumer: Optional[StreamingChunksConsumer] = None,
    ) -> ChatCompletionResult:
        """Legacy text completions CONTINUE the prompt verbatim — no chat
        template (OpenAI /v1/completions semantics)."""
        prompt_tokens = self.tokenizer.encode("".join(prompt))
        return await self._generate(prompt_tokens, options, stream_consumer)

    async def _generate(
        self,
        prompt_tokens: List[int],
        options: Dict[str, Any],
        stream_consumer: Optional[StreamingChunksConsumer] = None,
    ) -> ChatCompletionResult:
        from langstream_tpu.providers.jax_local.engine import SamplingParams

        wait = self.available()
        if wait is not None:
            # degraded mode: the supervisor is mid-rebuild — bounce NEW
            # work with a typed retryable error (503 + Retry-After on
            # the HTTP surfaces) before spending any engine work; the
            # engine's own submit() backstops the race
            from langstream_tpu.api import errors as api_errors

            raise api_errors.EngineRebuildingError(
                "engine is rebuilding after a crash; retry shortly",
                retry_after_s=wait,
            )
        sampling = SamplingParams(
            temperature=float(options.get("temperature") or 0.0),
            top_k=int(options.get("top-k") or 0),
            top_p=float(options.get("top-p") or 0.0),
            max_new_tokens=int(options.get("max-tokens") or 256),
            presence_penalty=float(options.get("presence-penalty") or 0.0),
            frequency_penalty=float(options.get("frequency-penalty") or 0.0),
            seed=(
                int(options["seed"]) if options.get("seed") is not None
                else None
            ),
            logit_bias=(
                {int(k): float(v) for k, v in options["logit-bias"].items()}
                if options.get("logit-bias") else None
            ),
        )
        session_id = options.get("session-id")
        # OpenAI-style stop STRINGS (`stop:` agent config): generation is
        # cancelled at the next token boundary once one appears in the
        # decoded text, and the result is trimmed at the match
        # (reference: ChatCompletionsConfig stop list)
        stop = options.get("stop") or []
        if isinstance(stop, str):
            stop_strings = [stop]
        elif isinstance(stop, (list, tuple)):
            # coerce entries: YAML users write bare numbers/bools too
            stop_strings = [str(s) for s in stop if s is not None and s != ""]
        else:
            stop_strings = [str(stop)]
        handle: list = []
        released_parts: list = []
        retained = [""]
        stop_cut: list = []
        holdback = max((len(s) for s in stop_strings), default=1) - 1

        def watch_stop(delta: str, final: bool = False) -> str:
            """Watch the streamed text; on a stop match, cancel the
            request and release only the text BEFORE the match. Withholds
            the last ``len(longest stop) - 1`` chars until cleared so a
            stop string split across two deltas never partially leaks
            into the stream (released at ``final`` if no match). Only the
            retained tail + the new delta are ever scanned — matches
            wholly inside the retained window were ruled out last round
            — so the per-token cost is O(delta), not O(answer)."""
            if not stop_strings:
                return delta
            if stop_cut:
                return ""
            window = retained[0] + delta
            hits = [
                position for position in
                (window.find(s) for s in stop_strings)
                if position != -1
            ]
            if hits:
                release = window[: min(hits)]
                retained[0] = ""
                stop_cut.append(True)
                if handle:
                    handle[0].cancel()
            elif final:
                release = window
                retained[0] = ""
            else:
                keep = min(holdback, len(window))
                release = window[: len(window) - keep]
                retained[0] = window[len(window) - keep:]
            if release:
                released_parts.append(release)
            return release

        answer_id = uuid.uuid4().hex
        on_token = None
        decoder = None
        index_box = [0]
        last_sent = [False]
        if stream_consumer is not None:
            decoder = self.tokenizer.stream_decoder()

            def on_token(token_id: int, is_last: bool) -> None:
                text = decoder.push(token_id)
                if is_last:
                    # deliver any bytes the decoder was withholding as a
                    # possible partial UTF-8 sequence — last chance
                    text += decoder.flush()
                text = watch_stop(text, final=is_last)
                if text or is_last:
                    index = index_box[0]
                    index_box[0] += 1
                    if is_last:
                        last_sent[0] = True
                    stream_consumer.consume_chunk(
                        answer_id, index,
                        ChatChunk(content=text, index=index),
                        last=is_last,
                    )

        elif stop_strings:
            # no streaming: still watch the decoded text so long answers
            # cancel at the stop instead of decoding to max-tokens
            non_stream_decoder = self.tokenizer.stream_decoder()

            def on_token(token_id: int, is_last: bool) -> None:
                watch_stop(non_stream_decoder.push(token_id))

        result = await self.engine.generate(
            prompt_tokens,
            sampling,
            stop_tokens=set(self.tokenizer.eos_ids),
            on_token=on_token,
            session_id=session_id,
            handle=handle,
            trace_id=(
                str(options["trace-id"]) if options.get("trace-id") else None
            ),
        )
        if stop_cut:
            # the stream watcher found the stop: the final content IS the
            # released stream (a batch re-decode can place multi-byte
            # replacement boundaries differently than the incremental
            # decoder, so re-finding the stop there could disagree)
            text = "".join(released_parts)
        else:
            text = self.tokenizer.decode(result.tokens)
        stop_trimmed = False
        if stop_strings and not stop_cut:
            for s in stop_strings:
                cut = text.find(s)
                if cut != -1:
                    text = text[:cut]
                    stop_trimmed = True
        kept_tokens = result.tokens
        kept_logprobs = result.logprobs
        kept_tops = result.top_logprobs
        if stop_cut or stop_trimmed:
            # drop the tokens past the stop so per-token data (logprobs,
            # completion_tokens) aligns with the trimmed content — the
            # engine decodes a few chunk-boundary tokens past the match
            # before the cancel lands
            walker = self.tokenizer.stream_decoder()
            length = 0
            kept = 0
            for token in result.tokens:
                length += len(walker.push(token))
                if length > len(text):
                    break
                kept += 1
            kept_tokens = result.tokens[:kept]
            kept_logprobs = result.logprobs[:kept]
            if kept_tops is not None:
                kept_tops = kept_tops[:kept]
        if stream_consumer is not None and not last_sent[0]:
            # terminal marker for chunk batchers when the stop token arrived
            # without a trailing streamed delta (on_token is not called for
            # stop tokens, so no last=True was emitted yet)
            tail = watch_stop(decoder.flush(), final=True)
            stream_consumer.consume_chunk(
                answer_id, index_box[0],
                ChatChunk(content=tail, index=index_box[0]),
                last=True,
            )
        want_logprobs = bool(options.get("logprobs"))
        finish_reason = result.finish_reason
        if stop_cut or stop_trimmed:
            finish_reason = "stop"  # a stop STRING ended the answer
        return ChatCompletionResult(
            content=text,
            finish_reason=finish_reason,
            prompt_tokens=result.prompt_tokens,
            completion_tokens=len(kept_tokens),
            # per-token decode only when the caller asked for logprobs —
            # N tokenizer round-trips are pure waste on the common path
            tokens=(
                [self.tokenizer.decode([t]) for t in kept_tokens]
                if want_logprobs else None
            ),
            logprobs=list(kept_logprobs) if want_logprobs else None,
            # K × tokens single-token decodes: only when the request
            # actually asked for alternatives (top-logprobs > 0), not
            # for every logprobs:true call on an enabled engine
            top_logprobs=(
                [
                    [
                        (self.tokenizer.decode([int(tid)]), float(tlp))
                        for tid, tlp in zip(ids, lps)
                    ]
                    for ids, lps in kept_tops
                ]
                if want_logprobs and kept_tops is not None
                and int(options.get("top-logprobs") or 0) > 0
                else None
            ),
        )

    async def close(self) -> None:
        if self._supervisor is not None:
            self._supervisor.stop()  # owns its watchdog + engine
            return
        if self.watchdog is not None:
            self.watchdog.stop()
        self.engine.stop()


class JaxEmbeddingsService(EmbeddingsService):
    def __init__(self, config: Dict[str, Any], model: Optional[str]) -> None:
        from langstream_tpu.providers.jax_local.embeddings import (
            EncoderConfig,
            JaxEmbedder,
            init_encoder_params,
            load_hf_bert,
        )
        from langstream_tpu.providers.jax_local.tokenizer import get_tokenizer

        embeddings_config = config.get("embeddings-model", {}) or {}
        checkpoint = embeddings_config.get("checkpoint") or (
            model if model and "/" in str(model) else None
        )
        if checkpoint:
            encoder_config, params = load_hf_bert(checkpoint)
            from langstream_tpu.providers.jax_local.tokenizer import HFTokenizer

            tokenizer = HFTokenizer(checkpoint)
        else:
            encoder_config = EncoderConfig.from_dict(
                embeddings_config if embeddings_config else {"preset": "tiny"}
            )
            params = init_encoder_params(encoder_config)
            tokenizer = get_tokenizer(config.get("tokenizer"))
            if not embeddings_config:
                logger.warning(
                    "jax-local embeddings: no checkpoint — random tiny encoder"
                )
        self.embedder = JaxEmbedder(
            encoder_config, params, tokenizer,
            max_length=int(embeddings_config.get("max-length", 256)),
        )

    async def compute_embeddings(self, texts: List[str]) -> List[List[float]]:
        # run the device call off the event loop
        return await asyncio.get_running_loop().run_in_executor(
            None, self.embedder.embed, texts
        )


class JaxLocalServiceProvider(ServiceProvider):
    """Service instances are cached per resource entry by
    :class:`~langstream_tpu.providers.registry.ServiceProviderRegistry`,
    which is what guarantees one engine per resource."""

    name = "jax-local"

    def supports(self, resource_config: Dict[str, Any]) -> bool:
        return (
            resource_config.get("type") in ("jax-local", "jax")
            or "jax-local" in resource_config
        )

    def get_completions_service(self, resource_config: Dict[str, Any]) -> CompletionsService:
        return JaxCompletionsService(resource_config)

    def get_embeddings_service(
        self, resource_config: Dict[str, Any], model: Optional[str] = None
    ) -> EmbeddingsService:
        return JaxEmbeddingsService(resource_config, model)
