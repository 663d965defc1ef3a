"""Cluster worker: one ContinuousBatchEngine in a role, behind HTTP.

A worker is a :class:`~paddle_tpu.serving_http.CompletionServer` (same
engine thread, same observability surface) plus the cluster contract:

- **membership** — it registers a lease heartbeat + a metadata record
  (address, role, kv channel) through ``distributed/elastic.py``'s
  ElasticManager, so the router's WorkerPool discovers it through the
  store like trainers discover peers;
- **role** — ``unified`` serves completions end to end; ``prefill``
  serves ``POST /v1/prefill`` (bucketed prefill → KV bundle shipped to a
  decode worker's handoff channel) and refuses completions; ``decode``
  additionally accepts completions whose prompt KV arrives by
  ``handoff_id`` instead of running the prefill itself;
- **/health** — gains ``role``, ``replica_id``, ``lease_age_s`` and
  ``draining`` so a load balancer (and the router's aggregate /health)
  sees both what a worker is and how fresh its membership claim is;
- **drain / migration** — ``POST /drain`` stops admission and reports
  the live request ids; ``POST /v1/migrate_out`` exports one decoding
  slot as a sealed bundle, ships it to a peer's handoff channel, and
  ends the departing SSE stream with a migrate marker (the router's
  relay follows it); ``POST /v1/release`` gives up the pool lease once
  the drain emptied the worker.

``python -m paddle_tpu.serving_cluster.worker '<json cfg>'`` is the
process entry the launcher (scripts/serve_cluster.py) spawns.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
import uuid
from typing import Optional

from ..analysis.threads.witness import make_lock
from ..chaos import inject as _chaos
from ..distributed.elastic import ElasticManager
from ..distributed.log_utils import get_logger
from ..serving_http import (CompletionServer, EngineCommand, _Submission,
                            apply_deadline_header)
from .kv_handoff import KvHandoffReceiver, make_receiver, open_sender

__all__ = ["WorkerServer", "run_worker", "build_model", "MODEL_BUILDERS"]

ROLES = ("prefill", "decode", "unified")


class _ExportPrefill(EngineCommand):
    """Engine-thread command: run the bucketed prefill for one prompt and
    return its host-side KV bundle (no slot taken)."""

    def __init__(self, ids, max_new_tokens: int):
        super().__init__()
        self.ids = ids
        self.max_new_tokens = max_new_tokens

    def execute(self, engine):
        return engine.export_prefill(self.ids,
                                     max_new_tokens=self.max_new_tokens)


class _ListLive(EngineCommand):
    """Engine-thread command: the live request ids by lifecycle stage —
    what a drain still has to move (active slots migrate; queued and
    mid-prefill requests become active first and migrate next round)."""

    def execute(self, engine):
        d = engine.debug_state()
        return {
            "active": [s["rid"] for s in d["slots"] if s is not None],
            "queued": list(d["queue"]),
            "prefilling": [v["rid"] for v in d["prefilling"].values()],
        }


class _ExportSlot(EngineCommand):
    """Engine-thread command: export one decoding slot as a migration
    bundle and detach its live submission (the handler thread ships the
    bundle and ends the stream with a migrate marker)."""

    def __init__(self, server: "WorkerServer", rid: int):
        super().__init__()
        self.server = server
        self.rid = rid

    def execute(self, engine):
        sub = self.server._live_subs.get(self.rid)
        if sub is not None and sub.n > 1:
            raise ValueError(
                f"request {self.rid} is one of n={sub.n} sibling "
                "completions — sibling groups finish locally instead of "
                "migrating")
        bundle = engine.export_slot(self.rid)
        self.server._live_subs.pop(self.rid, None)
        return bundle, sub


class _AdmitMigrated(EngineCommand):
    """Engine-thread command: re-admit an exported bundle LOCALLY — the
    fallback when the migration send fails after the slot was already
    exported (the stream continues here as if nothing happened)."""

    def __init__(self, server: "WorkerServer", bundle: dict, sub):
        super().__init__()
        self.server = server
        self.bundle = bundle
        self.sub = sub

    def execute(self, engine):
        sub = self.sub
        if sub is None:
            return engine.admit_migrated(self.bundle)
        ev = sub.events

        def on_token(rid, tok, done, logprob, _ev=ev):
            _ev.put(("token", (rid, tok, logprob), done))

        def on_shed(rid, info, _ev=ev):
            _ev.put(("shed", info, True))

        rid = engine.admit_migrated(self.bundle, on_token=on_token,
                                    trace_ctx=sub.trace_ctx,
                                    on_shed=on_shed)
        sub.rids.append(rid)
        self.server._live_subs[rid] = sub
        return rid


class WorkerServer(CompletionServer):
    """CompletionServer speaking the cluster protocol for one role."""

    def __init__(self, engine, *, role: str = "unified",
                 replica_id: int = 0,
                 elastic: Optional[ElasticManager] = None,
                 kv_receiver: Optional[KvHandoffReceiver] = None,
                 handoff_wait_s: float = 30.0, **kw):
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        super().__init__(engine, **kw)
        self.role = role
        self.replica_id = int(replica_id)
        self._elastic = elastic
        self._kv = kv_receiver
        self._handoff_wait_s = float(handoff_wait_s)
        self._senders = {}           # channel name -> KvHandoffSender
        self._senders_lock = make_lock("WorkerServer._senders_lock")
        # drain: admission stops, live slots migrate off, lease releases
        self.draining = False
        # rid -> live _Submission; ENGINE-THREAD ONLY (written in
        # _handle_submission and the migrate command, both of which run
        # on the engine thread) — the map that lets a migrate-out hand
        # the departing stream its marker event
        self._live_subs = {}
        if self._kv is not None:
            self._kv.start()

    def close(self):
        super().close()
        if self._kv is not None:
            self._kv.close()
        with self._senders_lock:
            senders, self._senders = dict(self._senders), {}
        for s in senders.values():
            s.close()

    # ---- cluster surface ------------------------------------------------
    def health_extra(self) -> dict:
        lease_age = (self._elastic.lease_age()
                     if self._elastic is not None else None)
        return {
            "role": self.role,
            "replica_id": self.replica_id,
            "lease_age_s": lease_age,
            "draining": self.draining,
            "kv_channel": (self._kv.name if self._kv is not None
                           else None),
        }

    def _handle_submission(self, sub):
        # engine thread: index live submissions by their engine rids so a
        # migrate-out can detach the right stream; pruned lazily against
        # the engine's live set (finished rids linger briefly, harmless)
        super()._handle_submission(sub)
        if isinstance(sub, _Submission):
            for rid in sub.rids:
                self._live_subs[rid] = sub
            if len(self._live_subs) > 4 * max(self.engine.max_batch, 1):
                eng = self.engine
                live = {r.rid for r in eng._slots if r is not None}
                live |= {r.rid for r in eng._queue}
                live |= {st.req.rid
                         for st in getattr(eng, "_chunking", {}).values()}
                self._live_subs = {rid: s
                                   for rid, s in self._live_subs.items()
                                   if rid in live}

    def _post_handler(self, route):
        fn = self._route_post(route)
        if fn is None:
            return None
        fault = _chaos.on("worker.request", route=route)
        if fault is not None:
            if fault.action == "http_500":
                return lambda handler, req: handler._json(
                    500, {"error": "chaos: injected worker fault"})
            if fault.action == "stall_heartbeat":
                if self._elastic is not None:
                    self._elastic.pause_heartbeat(
                        fault.duration_s or 3.0 * self._elastic.ttl)
            elif fault.action == "delay":
                time.sleep(fault.delay_s)
        return fn

    def _route_post(self, route):
        if route == "/drain":
            return self._drain_post
        if route == "/v1/migrate_out":
            return self._migrate_out_post
        if route == "/v1/release":
            return self._release_post
        if route == "/v1/prefill" and self.role in ("prefill", "unified"):
            return self._prefill_post
        return super()._post_handler(route)

    # ---- drain / migration ----------------------------------------------
    def _drain_post(self, handler, req):
        """Stop admission and report what is still live. Idempotent: the
        router's drain loop re-POSTs to watch the worker empty out while
        it migrates the active slots via /v1/migrate_out."""
        self.draining = True
        try:
            live = self.submit_command(_ListLive())
        except Exception as e:
            return handler._json(500, {"error": f"{type(e).__name__}: {e}"})
        return handler._json(200, {"draining": True,
                                   "replica_id": self.replica_id, **live})

    def _migrate_out_post(self, handler, req):
        """Export one decoding slot and ship it to a peer's handoff
        channel. The departing stream ends with a migrate marker naming
        the handoff id + destination; if the SEND fails, the bundle is
        re-admitted locally so the stream continues here instead of
        stranding the client."""
        try:
            rid = int(req["rid"])
            channel = req.get("channel")
            if not channel:
                raise ValueError("migrate_out needs 'channel' — the "
                                 "destination worker's kv handoff channel")
            dst = req.get("dst")
            hid = str(req.get("handoff_id") or uuid.uuid4().hex)
        except (KeyError, TypeError, ValueError) as e:
            return handler._json(400, {"error": str(e)})
        try:
            bundle, sub = self.submit_command(_ExportSlot(self, rid))
        except ValueError as e:
            # not actively decoding (queued / prefilling / finished) or
            # an n>1 sibling group: nothing exported, caller may retry
            # next drain round
            return handler._json(409, {"error": str(e)})
        except Exception as e:
            return handler._json(500, {"error": f"{type(e).__name__}: {e}"})
        generated = int(len(bundle["tokens"]))
        try:
            nbytes = self._sender(channel).send(hid, bundle)
        except Exception as e:
            get_logger().warning(
                "migrate_out %s -> %s failed (%s: %s); re-admitting "
                "locally", hid, channel, type(e).__name__, e)
            self.submit_command(_AdmitMigrated(self, bundle, sub))
            return handler._json(502, {
                "error": f"migration send failed ({type(e).__name__}: "
                         f"{e}); request re-admitted locally"})
        if sub is not None:
            sub.events.put(("migrated",
                            {"handoff_id": hid, "dst": dst,
                             "generated": generated}, True))
        return handler._json(200, {
            "handoff_id": hid, "channel": channel, "dst": dst,
            "rid": rid, "generated": generated, "bytes": nbytes,
        })

    def _release_post(self, handler, req):
        """Release the pool lease after a drain: the pool sees the lease
        lapse (no churn alarm — the drain was deliberate) and the worker
        process can be torn down at leisure."""
        if not self.draining:
            return handler._json(409, {
                "error": "release without drain — POST /drain first"})
        if self._elastic is not None:
            self._elastic.mark_done()
        return handler._json(200, {"released": True,
                                   "replica_id": self.replica_id})

    # ---- completions (decode side of the handoff) -----------------------
    def _complete(self, handler, req):
        if self.draining:
            # admission is closed; the router's placement already skips
            # draining workers, so this only catches racing requests
            return handler._json(503, {
                "error": f"worker {self.replica_id} is draining; "
                         "re-place this request"})
        if "handoff_id" in req:
            if self._kv is None:
                return handler._json(409, {
                    "error": f"this {self.role}-role worker has no kv "
                             "handoff channel"})
            return self._complete_from_handoff(handler, req)
        if self.role == "prefill":
            # a prefill-role worker holds no decode slots; the router
            # must not fall back to it for full completions
            return handler._json(409, {
                "error": "prefill-role worker serves /v1/prefill only"})
        return super()._complete(handler, req)

    def _complete_from_handoff(self, handler, req):
        hid = str(req["handoff_id"])
        bundle = self._kv.wait(hid, timeout=self._handoff_wait_s)
        if bundle is None:
            # the sender never delivered (died mid-handoff, or the
            # transport dropped the bundle): a 5xx here is what turns
            # into a router retry
            return handler._json(504, {
                "error": f"kv handoff {hid} not received within "
                         f"{self._handoff_wait_s}s"})
        sp = handler._trace_span
        trace_ctx = ((sp.trace_id, sp.span_id) if sp is not None else None)
        cid = f"cmpl-{uuid.uuid4().hex[:24]}"
        if bundle.get("kind") == "migrate":
            # migration continuation: every decode-side knob rides the
            # bundle; the stream emits only NEW tokens (the relay already
            # delivered the rest), a collect prepends them
            sub = _Submission(None, {}, handoff=bundle,
                              trace_ctx=trace_ctx)
            self._subs.put(sub)
            want_logprobs = bool(bundle.get("want_logprobs"))
            if req.get("stream"):
                return self._stream(handler, sub, cid, want_logprobs)
            prior = [int(t) for t in bundle["tokens"]]
            prior_lp = [float(x) for x in bundle.get("logprobs") or []]
            return self._collect(handler, sub, cid,
                                 int(bundle["prompt_tokens"]),
                                 want_logprobs, prior_tokens=prior,
                                 prior_logprobs=prior_lp)
        try:
            params, want_logprobs = self._parse_decode_params(req)
        except (ValueError, TypeError) as e:
            return handler._json(400, {"error": str(e)})
        # the router's deadline header carries the REMAINING budget —
        # the decode-side admission deadline derives from it, never a
        # fresh one (the prefill hop's time is already charged)
        err = apply_deadline_header(handler, params)
        if err is not None:
            return handler._json(*err)
        sub = _Submission(None, params, handoff=bundle,
                          trace_ctx=trace_ctx)
        self._subs.put(sub)
        n_prompt = int(bundle["prompt_tokens"])
        if req.get("stream"):
            return self._stream(handler, sub, cid, want_logprobs)
        return self._collect(handler, sub, cid, n_prompt, want_logprobs)

    def _parse_decode_params(self, req):
        """The decode-side subset of the completion params (the prompt
        lives in the handoff bundle): token budget, sampling overrides,
        stops, logprobs."""
        max_tokens = int(req.get("max_tokens", 16))
        if max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        params = dict(max_new_tokens=max_tokens)
        if ("temperature" in req or "top_p" in req
                or "top_k" in req or req.get("do_sample")):
            params.update(
                do_sample=True,
                temperature=float(req.get("temperature", 1.0)),
                top_k=int(req.get("top_k", 0)),
                top_p=float(req.get("top_p", 1.0)))
        stop = req.get("stop_token_ids")
        if stop is not None:
            params["stop_token_ids"] = [int(s) for s in stop]
        # SLO-aware scheduling rides the decode side: the decode worker
        # owns the slot pool the priority/deadline queue feeds
        if req.get("priority") is not None:
            params["priority"] = int(req["priority"])
        if req.get("slo_ms") is not None:
            slo = float(req["slo_ms"])
            if slo <= 0:
                raise ValueError("slo_ms must be > 0")
            params["slo_ms"] = slo
        # the router's request identity: the deathnote names it, so
        # poison blame follows the request across workers and retries
        if req.get("request_id") is not None:
            params["request_id"] = str(req["request_id"])
        lp_req = req.get("logprobs")
        want_logprobs = (lp_req is not None and lp_req is not False)
        if want_logprobs:
            params["logprobs"] = True
        return params, want_logprobs

    # ---- the prefill hop -------------------------------------------------
    def _prefill_post(self, handler, req):
        if self.draining:
            return handler._json(503, {
                "error": f"worker {self.replica_id} is draining"})
        try:
            ids = self._prompt_ids(req)
            max_tokens = int(req.get("max_tokens", 16))
            if max_tokens < 1:
                raise ValueError("max_tokens must be >= 1")
            channel = req.get("channel")
            if not channel:
                raise ValueError(
                    "prefill needs 'channel' — the decode worker's kv "
                    "handoff channel name")
            hid = str(req.get("handoff_id") or uuid.uuid4().hex)
        except (ValueError, TypeError) as e:
            return handler._json(400, {"error": str(e)})
        try:
            # the prefill runs ON the engine thread (only device-state
            # toucher); the shm push happens HERE on the handler thread —
            # the bundle is host numpy by then, and a full ring must
            # stall this request, not the engine loop
            bundle = self.submit_command(
                _ExportPrefill(ids, max_tokens))
            nbytes = self._sender(channel).send(hid, bundle)
        except (ValueError, TypeError, NotImplementedError) as e:
            return handler._json(400, {"error": str(e)})
        except Exception as e:
            get_logger().warning("prefill handoff %s -> %s failed "
                                 "(%s: %s)", hid, channel,
                                 type(e).__name__, e)
            return handler._json(500, {"error": f"{type(e).__name__}: {e}"})
        return handler._json(200, {
            "handoff_id": hid,
            "channel": channel,
            "prompt_tokens": int(bundle["prompt_tokens"]),
            "bytes": nbytes,
        })

    def _sender(self, channel: str):
        with self._senders_lock:
            s = self._senders.get(channel)
            if s is None:
                s = open_sender(channel)
                self._senders[channel] = s
            return s


# ---- model construction in the worker process -------------------------------

def _tiny_llama(spec: dict):
    from ..models.llama import LlamaConfig, LlamaForCausalLM

    kw = {k: spec[k] for k in ("num_hidden_layers", "hidden_size",
                               "num_attention_heads",
                               "num_key_value_heads") if k in spec}
    return LlamaForCausalLM(LlamaConfig.tiny(**kw))


MODEL_BUILDERS = {"tiny_llama": _tiny_llama}


def build_model(spec: dict):
    """Build the worker's model from its config spec: a registry ``kind``
    or a dotted ``factory`` ("pkg.module:fn", called with the spec).
    Weights must be DETERMINISTIC given the spec (every worker seeds
    before building) — prefill and decode engines only interoperate over
    identical weights."""
    import paddle_tpu as paddle

    paddle.seed(int(spec.get("seed", 0)))
    factory = spec.get("factory")
    if factory:
        mod_name, _, fn_name = factory.partition(":")
        if not fn_name:
            raise ValueError(
                f"factory must look like 'pkg.module:fn', got {factory!r}")
        import importlib

        return getattr(importlib.import_module(mod_name), fn_name)(spec)
    kind = spec.get("kind")
    if kind not in MODEL_BUILDERS:
        raise ValueError(f"unknown model kind {kind!r} "
                         f"(have {sorted(MODEL_BUILDERS)})")
    return MODEL_BUILDERS[kind](spec)


# ---- process entry ----------------------------------------------------------

class NoDevice(RuntimeError):
    """JAX found no device this process may use."""


def run_worker(cfg: dict):
    """Build the engine, join the pool, serve until SIGTERM.

    Config keys: ``replica_id``, ``role``, ``store`` (TCPStore
    host:port), ``world_size``, ``job_id``, ``ttl``, ``host``/``port``,
    ``model`` (builder spec), ``engine`` (ContinuousBatchEngine kwargs),
    ``platform`` (jax platform override), ``kv_capacity_mb``,
    ``incident_dir``. The persistent compile cache is where
    ``utils.compile_cache`` puts it for every process of this checkout.

    Raises :class:`NoDevice` before any model is built when JAX cannot
    acquire a device (on a TPU host: another process holds the chip).
    """
    import jax

    from ..utils import compile_cache

    platform = cfg.get("platform")
    if platform:
        jax.config.update("jax_platforms", platform)
    compile_cache.enable()
    try:
        jax.devices()
    except RuntimeError as e:
        raise NoDevice(str(e)) from e
    from ..serving import ContinuousBatchEngine

    replica_id = int(cfg.get("replica_id", 0))
    role = cfg.get("role", "unified")
    job_id = cfg.get("job_id", "serve")
    ttl = float(cfg.get("ttl", 5.0))
    if cfg.get("incident_dir"):
        from ..observability.flightrecorder import install_reporter

        install_reporter(cfg["incident_dir"])
    # chaos: a plan exported by the launcher/dryrun installs here with
    # this worker's scope, arming the in-process injection points
    # (kv_handoff.send, worker.request, worker.step)
    injector = _chaos.install_from_env(scope=f"worker:{replica_id}")

    model = build_model(cfg.get("model", {}))
    engine = ContinuousBatchEngine(model, **cfg.get("engine", {}))
    # the sentinel records the model spec into divergence bundles so
    # scripts/replay_divergence.py can rebuild the model offline
    engine.sentinel.model_spec = cfg.get("model", {})
    if injector is not None:
        _chaos.arm_engine(engine, injector)
    if cfg.get("deathnote"):
        # supervised worker: arm the pre-dispatch blame record so a
        # crash mid-dispatch names exactly the rids it died stepping
        from .supervisor import Deathnote

        engine.deathnote = Deathnote(cfg["deathnote"])

    kv_receiver = None
    if role in ("decode", "unified"):
        kv_receiver = make_receiver(
            name=f"/pdtpu_kv_{job_id}_{replica_id}_{os.getpid()}",
            capacity_mb=int(cfg.get("kv_capacity_mb", 64)))

    elastic = ElasticManager(endpoint=cfg["store"], rank=replica_id,
                             world_size=int(cfg.get("world_size", 1)),
                             ttl=ttl, job_id=job_id)
    srv = WorkerServer(engine, role=role, replica_id=replica_id,
                       elastic=elastic, kv_receiver=kv_receiver,
                       handoff_wait_s=float(cfg.get("handoff_wait_s",
                                                    30.0)),
                       model_name=cfg.get("model_name", "paddle-tpu"),
                       host=cfg.get("host", "127.0.0.1"),
                       port=int(cfg.get("port", 0)),
                       # correctness-sentinel knobs (None defers to the
                       # PDTPU_AUDIT_RATE / PDTPU_CANARY_INTERVAL_S /
                       # PDTPU_DIVERGENCE_DIR environment)
                       audit_rate=cfg.get("audit_rate"),
                       canary_interval_s=cfg.get("canary_interval_s"),
                       divergence_dir=cfg.get("divergence_dir"))
    srv.start()
    host, port = srv.address
    # lease first, metadata second: the pool only reads metadata for
    # ranks whose lease is already fresh, so a half-registered worker is
    # invisible rather than half-visible
    meta = {
        "host": host, "port": port, "role": role, "pid": os.getpid(),
        "kv_channel": kv_receiver.name if kv_receiver else None,
    }

    def _kv_meta():
        # prefix-hash summary + headroom for the router: the
        # prefix-affinity / capacity feedstock (ROADMAP items 3a, 4)
        atlas = getattr(engine, "kvatlas", None)
        return atlas.cluster_summary() if atlas is not None else None

    elastic.register()
    elastic.register_metadata(dict(meta, kv=_kv_meta()))
    get_logger().info("cluster worker %s (%s) serving on %s:%s",
                      replica_id, role, host, port)

    done = threading.Event()

    def _republish():
        # register_metadata is a plain store set, so the kv summary can
        # refresh on the lease cadence; the pool re-reads metadata for
        # alive ranks every refresh()
        while not done.wait(max(1.0, ttl / 2.0)):
            try:
                elastic.register_metadata(dict(meta, kv=_kv_meta()))
            except Exception:  # pdlint: disable=silent-exception -- a metadata refresh must never kill the serving worker; the stale summary just ages out
                pass

    threading.Thread(target=_republish, daemon=True,
                     name="kv-meta-republish").start()

    def _term(signum, frame):
        # clean teardown: deregister (peers must not read this exit as a
        # lapsed lease), stop serving, leave
        elastic.mark_done()
        done.set()

    signal.signal(signal.SIGTERM, _term)
    try:
        done.wait()
    except KeyboardInterrupt:
        elastic.mark_done()
    srv.close()


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python -m paddle_tpu.serving_cluster.worker "
              "'<json config>' | <config.json>", file=sys.stderr)
        return 2
    raw = argv[0]
    if raw.lstrip().startswith("{"):
        cfg = json.loads(raw)
    else:
        with open(raw, encoding="utf-8") as f:
            cfg = json.load(f)
    try:
        run_worker(cfg)
    except NoDevice as e:
        print(f"cluster worker {cfg.get('replica_id', 0)}: no JAX device "
              f"could be acquired — an accelerator belongs to one process "
              f"at a time, so run at most one worker per chip.\n{e}",
              file=sys.stderr, flush=True)
        from .supervisor import EXIT_NO_DEVICE

        return EXIT_NO_DEVICE
    return 0


if __name__ == "__main__":
    sys.exit(main())
