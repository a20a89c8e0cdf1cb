"""Failure classification for device calls that can fail.

The reference inherited fault tolerance from Spark for free: the
gradient job is a coarse functional computation, so a lost task is
recomputed from lineage (arXiv 1804.05839 §4).  Under JAX there is no
lineage — a failure surfaces as an exception out of a device call, and
everything downstream (retry, chunk downshift, emergency checkpoint,
replica failover) hinges on ONE question: is this failure transient
(the link hiccuped; the same call can succeed), is the backend gone
(retrying burns the run; checkpoint/failover instead), or is it a
programming error (retrying anywhere is wrong)?

``classify_error`` answers that from the exception type and message,
using marker sets distilled from real backend deaths.
"""
from __future__ import annotations


class TransientBackendError(RuntimeError):
    """A retryable failure: the operation may succeed if repeated
    (possibly with a smaller transfer)."""


class BackendLostError(RuntimeError):
    """The backend is gone for this process: retries cannot help.
    Callers should checkpoint / fail over / surface the loss — never
    spin against it."""


class ServingOverloaded(TransientBackendError):
    """Typed overload rejection: backpressure or admission control shed
    this request at enqueue.  Classified transient — the server is
    healthy but saturated, so the SAME request can succeed once load
    drains (retry with backoff, or route elsewhere).  Every raise of
    this type increments the ``serving/rejected_total`` obs counter,
    the accounting the SLO controller and goodput metric depend on."""


class ServingDeadlineExceeded(ServingOverloaded):
    """A request's wall-clock budget (``deadline_s``, minted at enqueue)
    expired before the server started useful work on it, so admission
    control shed it instead of prefilling an answer nobody is waiting
    for.  Subclassing :class:`ServingOverloaded` keeps every existing
    shed path honest for free: the SLO ladder, loadgen shed accounting,
    and ``serving/rejected_total`` all treat a blown deadline exactly
    like a backpressure rejection — the request was *not* lost, it was
    refused with a typed receipt."""


#: Substrings that mark a retryable wobble.  RESOURCE_EXHAUSTED is here on
#: purpose: for transfers the remedy is the chunk-size downshift that
#: rides the retry path.
TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "INTERNAL",
    "RESOURCE_EXHAUSTED",
    "Socket closed",
    "failed to connect",
    "Connection reset",
)

#: Substrings that mean the backend will not come back for this
#: process (a lost backend can only be restarted from outside it).
BACKEND_LOST_MARKERS = (
    "Unable to initialize backend",
    "backend lost",
    "Backend lost",
    "backend has been shut down",
)

#: Exception types that indicate a bug, not a backend: retrying them
#: anywhere (another attempt, another chunk size, another replica)
#: reproduces the same failure and wastes the window.
_FATAL_TYPES = (TypeError, ValueError, KeyError, IndexError,
                AttributeError, NotImplementedError, AssertionError)


def classify_error(exc: BaseException) -> str:
    """``"transient"`` | ``"backend_lost"`` | ``"fatal"``.

    Explicit resilience types win; then marker-string matching on
    ``type: message`` (JAX runtime errors carry the gRPC status in the
    message); unknown exceptions default to fatal — silently retrying
    a novel failure mode is how a bug hides as flakiness.
    """
    if isinstance(exc, BackendLostError):
        return "backend_lost"
    if isinstance(exc, TransientBackendError):
        return "transient"
    if isinstance(exc, _FATAL_TYPES):
        return "fatal"
    msg = f"{type(exc).__name__}: {exc}"
    for marker in BACKEND_LOST_MARKERS:
        if marker in msg:
            return "backend_lost"
    for marker in TRANSIENT_MARKERS:
        if marker in msg:
            return "transient"
    return "fatal"
