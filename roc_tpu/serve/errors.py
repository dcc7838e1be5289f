"""Typed serving failures: the ONLY ways a request is allowed to fail.

The robustness contract (tests/test_serve_robustness.py drills it
through the real export→load→load-generator path) is that an accepted
request either completes with a correct answer or fails with one of
these types — never a hang, never a bare RuntimeError, never a wrong
value.  Clients branch on the type; the router maps replica-side
failures onto the same vocabulary so one `except ServeError` covers a
single-process `Server` and a replicated `Router` alike.

Import-light on purpose (no jax, no numpy): the router's client side
imports these without a backend.
"""

from __future__ import annotations


class ServeError(RuntimeError):
    """Base class for every typed serving failure."""


class ServeTimeout(ServeError):
    """The request's ``deadline_ms`` expired before a dispatch could
    complete it.  Delivered at a microbatch boundary, so a deadline'd
    request resolves within ~one microbatch of its deadline — the
    "never a hang" half of the contract."""


class ServeOverload(ServeError):
    """Load shed: the bounded admission queue (or the router's
    in-flight cap) was full at submit time.  Raised immediately — an
    overloaded server fails fast instead of queueing unboundedly and
    timing everyone out."""


class ServeClosed(ServeError):
    """The server/router is closed (or draining): late ``submit()``
    calls are rejected with this instead of racing the dispatcher
    shutdown."""


class GatherError(ServeError):
    """The cross-shard gather leg failed: a sliced replica could not
    fetch rows it does not own at the microbatch's captured table
    version (owner refused the version pin twice, owner died
    mid-fetch, no gather path configured, or the microbatch's foreign
    set exceeded the staging halo).  Retryable at the router level —
    a re-dispatch captures a fresh version and gathers again."""


class ReplicaLost(ServeError):
    """Router-internal: the replica holding this request died.  Client
    code normally never sees it — the router requeues the request onto
    a surviving replica; it surfaces only when NO replica can serve
    the request's shard anymore."""
