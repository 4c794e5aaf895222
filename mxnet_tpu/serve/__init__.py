"""Production inference serving tier (docs/serving.md).

Three coupled pieces (ISSUE 8 tentpole):

- :mod:`.scheduler` — async continuous batching: bounded admission
  queue with backpressure, prefill/decode split over bucketed sequence
  lengths, slot recycling on EOS;
- :mod:`.arena` — paged KV-cache arena: block tables over fixed-size
  KV pages, one device buffer a layer and side, reuse gated on the
  engine's var-dependency tracking (``Engine.pending_reads``);
- :mod:`.model` — paged prefill/decode programs AOT-compiled from the
  geometry's shapes alone, with the weights as an argument: a PR 7
  ``MXAOT1`` bundle holds each program once and the weights once, so a
  serving process performs zero live jits and a reload swaps programs,
  weights and arena together.

ISSUE 13 stacked two decode multipliers on top: n-gram self-speculative
decoding (:mod:`.spec` proposes drafts, the bundle's compiled ``verify``
signature scores them, acceptance is exact so greedy output is identical
with speculation on or off) and an int8 paged-KV arena with per-page
quantization scales (``export_serving_bundle(..., kv_dtype="int8",
spec_k=4)``).

ISSUE 15 closed the request lifecycle under failure (docs/serving.md
"Robustness & deploys"): per-request deadlines and cancellation with
typed errors, graceful drain (503 + Retry-After), AOT bundle hot-swap
(``LlamaServer.reload``), serve-loop crash containment, and seeded
chaos coverage (``tests/test_serve_chaos.py``).

ISSUE 19 added cross-request KV reuse (docs/serving.md "Prefix caching,
sessions & chunked prefill"): a radix-tree :class:`PrefixCache` splices
already-prefilled prompt pages into new requests' block tables
(refcounted sharing over the arena's owner-checked free list), pinned
multi-turn chat sessions (``POST /v1/chat``) that prefill only each
turn's delta, and chunked prefill (``prefill_chunk``) that interleaves
long prompts with decode steps — greedy output stays token-for-token
identical cache-on vs cache-off.

ISSUE 18 lifted those per-replica primitives to a fleet
(:mod:`.fleet`, docs/serving.md "Fleet serving"): a
:class:`FleetRouter` HTTP front over N replicas with queue-depth-aware
power-of-two-choices routing, bounded retries + opt-in hedging,
circuit-breaker ejection/re-admission, and chaos-verified
``rolling_deploy`` with zero dropped requests.

Quick start::

    from mxnet_tpu import serve
    from mxnet_tpu.gluon.model_zoo.llama import llama_small

    net = llama_small(); net.initialize()
    serve.export_serving_bundle(net, "llama.mxaot",
                                page_size=8, num_pages=64, max_batch=4,
                                prefill_buckets=(16, 32))
    with serve.LlamaServer("llama.mxaot") as srv:
        tokens = srv.generate([1, 2, 3], max_new_tokens=16)
"""
from .arena import PagedKVArena
from .fleet import (FleetNoHealthyReplica, FleetRouter, HttpReplica,
                    LocalReplica)
from .model import (KVGeometry, check_geometry, export_serving_bundle,
                    geometry_from_net, load_serving_executables)
from .prefix import PrefixCache
from .scheduler import (Request, Scheduler, ServeCancelled,
                        ServeDeadlineExceeded, ServeDraining,
                        ServeInternalError, ServeQueueFull,
                        ServeSessionBusy, ServeSessionUnknown, ServeShutdown,
                        clamp_retry_after, greedy_sampler)
from .server import (AOTRunner, LlamaServer, drive_workload,
                     poisson_workload)
from .spec import NgramProposer, propose_ngram

__all__ = [
    "AOTRunner", "FleetNoHealthyReplica", "FleetRouter", "HttpReplica",
    "KVGeometry", "LlamaServer", "LocalReplica", "NgramProposer",
    "PagedKVArena", "PrefixCache", "Request",
    "Scheduler", "ServeCancelled", "ServeDeadlineExceeded",
    "ServeDraining", "ServeInternalError", "ServeQueueFull",
    "ServeSessionBusy", "ServeSessionUnknown",
    "ServeShutdown", "check_geometry", "clamp_retry_after",
    "drive_workload", "export_serving_bundle",
    "geometry_from_net", "greedy_sampler",
    "load_serving_executables", "poisson_workload", "propose_ngram",
]
