"""Proto-less gRPC transport.

The reference generates protobuf stubs from dlrover/proto/elastic_training.proto.
Here the master service is a single generic unary RPC ``/dlrover_tpu.Master/call``
carrying a schema'd JSON envelope ``{"v": 1, "m": method_name, "d": message}``
(codec: common/comm.py — typed dataclass registry, no pickle anywhere on
the network path); the servicer dispatches on ``method_name``. Identical
RPC semantics, no protoc toolchain, and a malformed or unknown payload
raises :class:`~dlrover_tpu.common.comm.WireError` instead of executing.
"""

import asyncio
import json
import socket
import threading
from concurrent import futures
from typing import Awaitable, Callable, Dict, Optional

import grpc
from grpc import aio as grpc_aio

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import GRPC
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import tracing

SERVICE_NAME = "dlrover_tpu.Master"
METHOD_NAME = "call"
WIRE_VERSION = 1


def _pack_call(method: str, message) -> bytes:
    return json.dumps({
        "v": WIRE_VERSION,
        "m": method,
        "d": comm._encode(message),
    }, separators=(",", ":")).encode("utf-8")


def _unpack_call(payload: bytes):
    try:
        doc = json.loads(payload.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise comm.WireError(f"request is not valid JSON: {e}")
    if not isinstance(doc, dict) or not isinstance(doc.get("m"), str):
        raise comm.WireError("request envelope malformed")
    if doc.get("v") != WIRE_VERSION:
        raise comm.WireError(
            f"unsupported wire version {doc.get('v')!r}"
        )
    return doc["m"], comm._decode(doc.get("d"))

def _trace_from_metadata(context):
    """Extract the caller's trace context from gRPC invocation metadata.

    Returns ``(trace_id, span_id)`` or ``(None, None)``; never raises —
    a garbled header from an old or foreign client must not fail the
    RPC it decorates."""
    try:
        metadata = context.invocation_metadata() or ()
    except Exception:
        return None, None
    for item in metadata:
        if item[0] == tracing.TRACE_METADATA_KEY:
            return tracing.parse_traceparent(item[1])
    return None, None


_GRPC_OPTIONS = [
    ("grpc.max_send_message_length", GRPC.MAX_SEND_MESSAGE_LENGTH),
    ("grpc.max_receive_message_length", GRPC.MAX_RECEIVE_MESSAGE_LENGTH),
]

# Client channels additionally cap gRPC's internal reconnect backoff.
# The library default grows it toward 120s after failed dials; a master
# that restarts on the same port (fail-over drills, reshard-in-place)
# can then sit reachable for its whole grace window while a wedged
# client's channel isn't even attempting to connect — every RPC and
# supervisor ping fails instantly from TRANSIENT_FAILURE in between
# dials. The ConnectionSupervisor owns outage pacing (decorrelated
# jitter, bounded deadline); the channel's job is just to re-dial
# promptly once asked.
_CLIENT_CHANNEL_OPTIONS = _GRPC_OPTIONS + [
    ("grpc.initial_reconnect_backoff_ms", 200),
    ("grpc.min_reconnect_backoff_ms", 200),
    ("grpc.max_reconnect_backoff_ms", 2000),
]


def find_free_port(host: str = "") -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, 0))
        return s.getsockname()[1]


def addr_connected(addr: str, timeout: float = 3.0) -> bool:
    host, _, port = addr.rpartition(":")
    try:
        with socket.create_connection((host, int(port)), timeout=timeout):
            return True
    except OSError:
        return False


#: dispatch pool size (the servicer's bounded admission keeps the
#: batched report path from monopolizing it)
MAX_WORKERS = 64


class GenericRpcServer:
    """gRPC server exposing one generic dispatch method."""

    def __init__(self, handler: Callable[[str, object], object],
                 port: int = 0):
        self._handler = handler
        # named threads: flight-recorder stack dumps must attribute
        # RPC work (a bare "ThreadPoolExecutor-0_3" frame is noise)
        self._server = grpc.server(
            futures.ThreadPoolExecutor(
                max_workers=MAX_WORKERS,
                thread_name_prefix="grpc-worker",
            ),
            options=_GRPC_OPTIONS,
        )
        rpc_handler = grpc.unary_unary_rpc_method_handler(
            self._dispatch,
            request_deserializer=None,  # raw bytes
            response_serializer=None,
        )
        service = grpc.method_handlers_generic_handler(
            SERVICE_NAME, {METHOD_NAME: rpc_handler}
        )
        self._server.add_generic_rpc_handlers((service,))
        self.port = self._server.add_insecure_port(f"[::]:{port}")

    def _dispatch(self, request_bytes: bytes, context) -> bytes:
        try:
            method, message = _unpack_call(request_bytes)
        except comm.WireError as e:
            # reject, never execute: schema violations are the caller's
            # fault (or an attack), not a server error
            logger.warning("rejected malformed RPC: %s", e)
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        try:
            tid, sid = _trace_from_metadata(context)
            with tracing.trace_context(tid, sid):
                result = self._handler(method, message)
            return comm.serialize(result)
        except Exception as e:
            logger.exception("RPC dispatch failed: %s", e)
            context.abort(grpc.StatusCode.INTERNAL, str(e))

    def start(self):
        self._server.start()

    def stop(self, grace: Optional[float] = None):
        self._server.stop(grace)

    def wait_for_termination(self, timeout=None):
        self._server.wait_for_termination(timeout)


class AsyncRpcServer:
    """Event-loop front end for the same generic dispatch method
    (ISSUE 16 tentpole a).

    One dedicated thread runs an asyncio loop hosting a ``grpc.aio``
    server. Dispatch splits two ways:

    * **hot lane** — methods in ``hot_handlers`` (the delta-report
      ingest) are ``async`` handlers awaited directly on the loop:
      parsing, admission and the shed ack cost no thread at all, and
      an accepted report's apply rides a sharded single-thread
      executor (master/ingest.py) — there is no thread per agent
      anywhere on the path;
    * **cold lane** — every other method (rendezvous, checkpoint
      consensus, KV, serving) dispatches to a bounded, named thread
      pool exactly like :class:`GenericRpcServer` — slow handlers
      keep their blocking idioms and can never stall the hot acks.

    Wire format, abort semantics and the client are unchanged: a
    :class:`GenericRpcClient` cannot tell the two servers apart.
    """

    def __init__(self, handler: Callable[[str, object], object],
                 port: int = 0,
                 hot_handlers: Optional[
                     Dict[str, Callable[[object], Awaitable[object]]]
                 ] = None):
        self._handler = handler
        self._hot = dict(hot_handlers or {})
        self._pool = futures.ThreadPoolExecutor(
            max_workers=MAX_WORKERS,
            thread_name_prefix="grpc-worker",
        )
        self._requested_port = port
        self.port = 0
        self._loop = asyncio.new_event_loop()
        self._server: Optional[grpc_aio.Server] = None
        self._ready = threading.Event()
        self._startup_error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run_loop, name="grpc-ingest-loop", daemon=True
        )
        self._thread.start()
        # the bound port must be known synchronously (callers publish
        # it before start()), so construction waits for the loop thread
        # to build and bind the aio server
        self._ready.wait(timeout=60.0)
        if self._startup_error is not None:
            raise RuntimeError(
                f"async rpc server failed to bind: {self._startup_error}"
            ) from self._startup_error
        if not self._ready.is_set():
            raise RuntimeError("async rpc server never became ready")

    # ------------------------------------------------------------ loop body

    def _run_loop(self):
        asyncio.set_event_loop(self._loop)
        try:
            self._server = grpc_aio.server(options=_GRPC_OPTIONS)
            rpc_handler = grpc.unary_unary_rpc_method_handler(
                self._dispatch,
                request_deserializer=None,  # raw bytes
                response_serializer=None,
            )
            service = grpc.method_handlers_generic_handler(
                SERVICE_NAME, {METHOD_NAME: rpc_handler}
            )
            self._server.add_generic_rpc_handlers((service,))
            self.port = self._server.add_insecure_port(
                f"[::]:{self._requested_port}"
            )
        except Exception as e:
            self._startup_error = e
            self._ready.set()
            return
        self._ready.set()
        try:
            self._loop.run_forever()
        finally:
            self._loop.close()

    async def _dispatch(self, request_bytes: bytes, context) -> bytes:
        try:
            method, message = _unpack_call(request_bytes)
        except comm.WireError as e:
            # reject, never execute: schema violations are the caller's
            # fault (or an attack), not a server error
            logger.warning("rejected malformed RPC: %s", e)
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        try:
            tid, sid = _trace_from_metadata(context)
            hot = self._hot.get(method)
            if hot is not None:
                with tracing.trace_context(tid, sid):
                    result = await hot(message)
            else:
                # contextvars do not cross run_in_executor; re-install
                # the caller's trace context on the pool thread so cold
                # handlers' spans still parent to the remote caller
                def _run_cold():
                    with tracing.trace_context(tid, sid):
                        return self._handler(method, message)
                result = await asyncio.get_running_loop().run_in_executor(
                    self._pool, _run_cold
                )
            return comm.serialize(result)
        except Exception as e:
            logger.exception("RPC dispatch failed: %s", e)
            await context.abort(grpc.StatusCode.INTERNAL, str(e))

    # ------------------------------------------------------------ lifecycle

    def start(self):
        fut = asyncio.run_coroutine_threadsafe(
            self._server.start(), self._loop
        )
        fut.result(timeout=30.0)

    def stop(self, grace: Optional[float] = None):
        # idempotent: a drill may kill the master and its fixture stop
        # it again — the second call must not touch the dead loop
        server, self._server = self._server, None
        if server is not None and self._loop.is_running():
            coro = server.stop(grace)
            try:
                fut = asyncio.run_coroutine_threadsafe(coro, self._loop)
            except RuntimeError as e:  # loop shut down under us
                coro.close()
                logger.warning("async rpc server stop: %s", e)
            else:
                try:
                    fut.result(timeout=(grace or 0.0) + 10.0)
                except Exception as e:
                    logger.warning("async rpc server stop: %s", e)
        if self._loop.is_running():
            self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5.0)
        self._pool.shutdown(wait=False)

    def wait_for_termination(self, timeout=None):
        self._thread.join(timeout)


class GenericRpcClient:
    """Client for GenericRpcServer; thread-safe, lazy channel."""

    def __init__(self, addr: str, timeout: float = 30.0):
        self.addr = addr
        self.timeout = timeout
        self._lock = threading.Lock()
        self._channel: Optional[grpc.Channel] = None
        self._callable = None

    def _ensure_channel(self):
        with self._lock:
            if self._channel is None:
                self._channel = grpc.insecure_channel(
                    self.addr, options=_CLIENT_CHANNEL_OPTIONS
                )
                self._callable = self._channel.unary_unary(
                    f"/{SERVICE_NAME}/{METHOD_NAME}",
                    request_serializer=None,
                    response_deserializer=None,
                )

    def call(self, method: str, message, timeout: Optional[float] = None):
        self._ensure_channel()
        # snapshot under the lock, dial outside it: the RPC itself must
        # never run under the channel lock (blocking-under-lock)
        with self._lock:
            fn = self._callable
        payload = _pack_call(method, message)
        tp = tracing.traceparent()
        response = fn(
            payload,
            timeout=timeout or self.timeout,
            metadata=(
                ((tracing.TRACE_METADATA_KEY, tp),) if tp else None
            ),
        )
        return comm.deserialize(response)

    def reset(self, addr: str):
        """Re-point the client at a new address (relay -> direct-master
        failover). The old channel closes outside the lock; in-flight
        calls on it fail with a connection error and retry on the new
        address through their supervisor."""
        with self._lock:
            old = self._channel
            self._channel = None
            self._callable = None
            self.addr = addr
        if old is not None:
            old.close()

    def close(self):
        with self._lock:
            if self._channel is not None:
                self._channel.close()
                self._channel = None
                self._callable = None
