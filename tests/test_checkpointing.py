"""Checkpoint transport tests. Mirrors reference checkpointing_test.py:17-105:
HTTP round-trip, step mismatch -> error, timeout behavior, lock gating."""

import os
import threading
import urllib.error
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.checkpointing import (
    CheckpointServer,
    deserialize_state_dict,
    serialize_state_dict,
)


@pytest.fixture
def server():
    s = CheckpointServer(timeout=timedelta(seconds=10))
    yield s
    s.shutdown()


def test_roundtrip_pytree(server):
    state = {
        "model": {"w": np.arange(12, dtype=np.float32).reshape(3, 4)},
        "opt": [np.ones(3, np.float64), 7],
        "step": 42,
    }
    server.send_checkpoint([1], step=5, state_dict=state, timeout=timedelta(seconds=5))
    out = server.recv_checkpoint(
        src_rank=0, metadata=server.metadata(), step=5, timeout=timedelta(seconds=5)
    )
    np.testing.assert_array_equal(out["model"]["w"], state["model"]["w"])
    np.testing.assert_array_equal(out["opt"][0], state["opt"][0])
    assert out["opt"][1] == 7 and out["step"] == 42


def test_roundtrip_jax_arrays(server):
    import jax.numpy as jnp

    state = {"w": jnp.arange(8, dtype=jnp.bfloat16)}
    server.send_checkpoint([1], step=0, state_dict=state, timeout=timedelta(seconds=5))
    out = server.recv_checkpoint(
        src_rank=0, metadata=server.metadata(), step=0, timeout=timedelta(seconds=5)
    )
    # Received on host as numpy with the dtype preserved.
    assert out["w"].dtype == jnp.bfloat16.dtype
    np.testing.assert_array_equal(
        np.asarray(out["w"], np.float32), np.arange(8, dtype=np.float32)
    )


def test_wrong_step_is_an_error(server):
    server.send_checkpoint([1], step=3, state_dict={"x": 1}, timeout=timedelta(seconds=5))
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        server.recv_checkpoint(
            src_rank=0,
            metadata=server.metadata(),
            step=4,
            timeout=timedelta(seconds=5),
        )
    assert exc_info.value.code == 400


def test_starts_disallowed_and_regates(server):
    # Before any send_checkpoint, reads block until the server-side timeout.
    fast = CheckpointServer(timeout=timedelta(milliseconds=100))
    try:
        with pytest.raises(Exception):
            fast.recv_checkpoint(
                src_rank=0,
                metadata=fast.metadata(),
                step=0,
                timeout=timedelta(seconds=5),
            )
        fast.send_checkpoint([1], 1, {"x": 1}, timeout=timedelta(seconds=5))
        assert (
            fast.recv_checkpoint(
                src_rank=0,
                metadata=fast.metadata(),
                step=1,
                timeout=timedelta(seconds=5),
            )["x"]
            == 1
        )
        # disallow_checkpoint re-locks the gate (manager.py:591 discipline).
        fast.disallow_checkpoint()
        with pytest.raises(Exception):
            fast.recv_checkpoint(
                src_rank=0,
                metadata=fast.metadata(),
                step=1,
                timeout=timedelta(seconds=5),
            )
    finally:
        fast.shutdown()


def test_allow_disallow_idempotent(server):
    server.disallow_checkpoint()
    server.disallow_checkpoint()
    server.allow_checkpoint(1)
    server.allow_checkpoint(2)
    out = server.recv_checkpoint(
        src_rank=0, metadata=server.metadata(), step=2, timeout=timedelta(seconds=5)
    )
    assert out is None  # no state dict was ever set


def test_concurrent_readers(server):
    state = {"w": np.ones((256, 256), np.float32)}
    server.send_checkpoint(
        [1, 2, 3], step=9, state_dict=state, timeout=timedelta(seconds=5)
    )
    results = []
    errors = []

    def fetch():
        try:
            results.append(
                server.recv_checkpoint(
                    0, server.metadata(), 9, timeout=timedelta(seconds=10)
                )
            )
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=fetch) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 4
    for r in results:
        np.testing.assert_array_equal(r["w"], state["w"])


def test_serialize_handles_scalars_and_none():
    tree = {"a": None, "b": 3.5, "c": [np.int64(2), "s"]}
    out = deserialize_state_dict(serialize_state_dict(tree))
    assert out == tree


def test_optax_state_roundtrips_through_safelist():
    # Real recovery payloads carry optax namedtuple states; the safelisted
    # unpickler must reconstruct them type-intact so tx.update still works.
    import jax
    import jax.numpy as jnp
    import optax

    params = {"w": jnp.ones((3,))}
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    out = deserialize_state_dict(
        serialize_state_dict({"params": params, "opt_state": opt_state})
    )
    restored = jax.tree_util.tree_map(jnp.asarray, out["opt_state"])
    updates, _ = tx.update(
        {"w": jnp.ones((3,))},
        restored,
        jax.tree_util.tree_map(jnp.asarray, out["params"]),
    )
    assert jax.tree_util.tree_structure(restored) == (
        jax.tree_util.tree_structure(opt_state)
    )


def test_malicious_pickle_rejected():
    # The classic RCE gadget must not resolve (reference posture is
    # torch.load(weights_only=False); this transport is stricter).
    import pickle

    class Evil:
        def __reduce__(self):
            return (os.system, ("echo pwned",))

    payload = pickle.dumps(Evil())
    with pytest.raises(pickle.UnpicklingError, match="disallowed global"):
        deserialize_state_dict(payload)


def test_safelist_not_extensible_from_payload():
    # The bypass class: a payload that calls register_safe_modules("os")
    # mid-load and then resolves os.system. Both hops must fail — functions
    # are never resolvable and the safelist is snapshotted per load.
    import pickle

    from torchft_tpu.checkpointing import register_safe_modules

    class Sneaky:
        def __reduce__(self):
            return (register_safe_modules, ("os",))

    with pytest.raises(pickle.UnpicklingError, match="disallowed global"):
        deserialize_state_dict(pickle.dumps(Sneaky()))

    class Evil:
        def __reduce__(self):
            return (os.system, ("echo pwned",))

    # ...and "os" must not have leaked into the process-global safelist.
    with pytest.raises(pickle.UnpicklingError, match="disallowed global"):
        deserialize_state_dict(pickle.dumps(Evil()))


def test_functions_in_safe_modules_rejected():
    # Class-only rule: numpy itself is safelisted, but a REDUCE on one of
    # its functions (arbitrary-call primitive) must not resolve.
    import pickle

    class FnGadget:
        def __reduce__(self):
            return (np.array, ([1, 2],))

    with pytest.raises(pickle.UnpicklingError, match="disallowed global"):
        deserialize_state_dict(pickle.dumps(FnGadget()))


def test_register_safe_modules_extends_allowlist():
    from torchft_tpu.checkpointing import (
        _SAFE_MODULE_ROOTS,
        register_safe_modules,
    )

    assert "fractions" not in _SAFE_MODULE_ROOTS
    import fractions
    import pickle

    payload = pickle.dumps(fractions.Fraction(1, 3))
    with pytest.raises(pickle.UnpicklingError):
        deserialize_state_dict(payload)
    register_safe_modules("fractions")
    try:
        assert deserialize_state_dict(payload) == fractions.Fraction(1, 3)
    finally:
        _SAFE_MODULE_ROOTS.discard("fractions")


def test_streaming_no_full_payload_buffer(server, monkeypatch):
    """The HTTP path must STREAM (reference checkpointing.py:139-170):
    chunked transfer on the wire, no serialize_state_dict() full-bytes
    buffer on the server, incremental unpickle on the receiver. The state
    is several times larger than any internal chunk, so a buffering
    implementation would materialize tens of MB here."""
    import urllib.request
    from datetime import timedelta

    import numpy as np

    from torchft_tpu import checkpointing as C

    def boom(_):
        raise AssertionError(
            "serialize_state_dict (full-payload buffer) used on the "
            "HTTP serving path"
        )

    monkeypatch.setattr(C, "serialize_state_dict", boom)
    big = {
        f"w{i}": np.random.default_rng(i).standard_normal((1 << 20,))
        for i in range(8)  # 8 x 8 MB leaves
    }
    server.send_checkpoint([1], step=3, state_dict=big,
                           timeout=timedelta(seconds=10))
    # wire-level check: chunked, no Content-Length
    with urllib.request.urlopen(f"{server.address()}3", timeout=10) as f:
        assert f.headers.get("Content-Length") is None
        assert f.headers.get("Transfer-Encoding") == "chunked"
        out = C.load_state_dict_stream(f)
    for k, v in big.items():
        np.testing.assert_array_equal(out[k], v)
    # stripes=1 selects the streamed client path directly (the striped
    # default trades this bounded-memory property for bandwidth, so it
    # must be pinned here for the assertion to mean anything)
    monkeypatch.setenv("TORCHFT_CKPT_STRIPES", "1")
    out2 = server.recv_checkpoint(
        0, server.address(), 3, timeout=timedelta(seconds=10)
    )
    np.testing.assert_array_equal(out2["w0"], big["w0"])


def test_striped_parallel_fetch_roundtrip(server):
    """The striped path: N byte ranges over N parallel connections
    (/checkpoint/{step}/part/{i}/{n}), reassembled and deserialized
    through the same safelist. Parts are ranged (Content-Length), not
    chunked — the server serves them from a per-step pickle cache."""
    import urllib.request

    big = {
        f"w{i}": np.random.default_rng(i).standard_normal((1 << 18,))
        for i in range(4)
    }
    server.send_checkpoint([1], step=9, state_dict=big,
                           timeout=timedelta(seconds=10))
    out = CheckpointServer.load_from_address(
        f"{server.address()}9", timeout=timedelta(seconds=10), stripes=4
    )
    for k, v in big.items():
        np.testing.assert_array_equal(out[k], v)
    with urllib.request.urlopen(f"{server.address()}9/part/0/4",
                                timeout=10) as f:
        assert f.headers.get("Content-Length") is not None
    # a part request for the wrong step is the same 400 contract
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(f"{server.address()}8/part/0/4", timeout=10)
    assert exc_info.value.code == 400
    # out-of-range part index is a 404, not a hang
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(f"{server.address()}9/part/4/4", timeout=10)
    assert exc_info.value.code == 404


def test_striped_fetch_falls_back_on_legacy_server(server, monkeypatch):
    """Against a pre-striping peer (no /part/ nor /stream/ endpoint ->
    404/500) the client must heal at single-stream speed, not fail."""
    import urllib.request

    state = {"w": np.arange(32, dtype=np.float32)}
    server.send_checkpoint([1], step=2, state_dict=state,
                           timeout=timedelta(seconds=10))
    real = urllib.request.urlopen

    def legacy(url, timeout=None):
        u = str(url)
        if "/part/" in u or "/stream" in u:
            raise urllib.error.HTTPError(u, 404, "no such path", {}, None)
        return real(url, timeout=timeout)

    monkeypatch.setattr(urllib.request, "urlopen", legacy)
    out = CheckpointServer.load_from_address(
        f"{server.address()}2", timeout=timedelta(seconds=10), stripes=4
    )
    np.testing.assert_array_equal(out["w"], state["w"])


# -- streamed zero-copy heal pipeline ---------------------------------------


def _donor_state():
    """A realistic heal payload: f32 params, optax adamw state (f32
    moments + int count), manager counters, and a non-array leaf mix."""
    import jax
    import jax.numpy as jnp
    import optax

    params = {
        "dense": jnp.asarray(
            np.random.default_rng(0).standard_normal((257, 31), np.float32)
        ),
        "bias": jnp.asarray(
            np.random.default_rng(1).standard_normal((31,), np.float32)
        ),
    }
    tx = optax.adamw(1e-3)
    opt_state = tx.init(params)
    # make the moments non-trivial so bf16 rounding is observable
    opt_state = jax.tree_util.tree_map(
        lambda l: l + 0.1234567 if hasattr(l, "dtype")
        and l.dtype == jnp.float32 else l,
        opt_state,
    )
    return {
        "user": {
            "params": params,
            "opt_state": opt_state,
            # f32 leaf OUTSIDE both params and opt_state: the bf16 wire
            # must protect-by-default (ship raw), not round it
            "ema_weights": jnp.asarray(
                np.random.default_rng(2).standard_normal((19,), np.float32)
            ),
        },
        "torchft": {"step": 17, "batches_committed": 51},
    }


@pytest.mark.parametrize("wire", [None, "bf16"])
@pytest.mark.parametrize("streams", [1, 2, 4])
def test_stream_heal_params_bit_identical(server, wire, streams):
    """The acceptance oracle: across every wire x stream-count
    combination, the healed replica's PARAMS are bit-identical to the
    donor's f32 buffers. The bf16 wire may round ONLY f32 leaves under
    an ``opt_state`` key (optimizer moments); everything else —
    params, and any leaf the predicate doesn't recognize — ships raw
    (protect-by-default)."""
    import jax

    state = _donor_state()
    server.send_checkpoint([1], step=7, state_dict=state,
                           timeout=timedelta(seconds=10))
    out, stats = CheckpointServer._fetch(
        f"{server.address()}7", timeout=timedelta(seconds=10),
        wire=wire, streams=streams,
    )
    assert stats["path"] == "stream"
    assert stats["streams"] == streams and stats["wire"] == wire
    for key in ("dense", "bias"):
        donor = np.asarray(state["user"]["params"][key])
        healed = np.asarray(out["user"]["params"][key])
        assert healed.dtype == donor.dtype
        assert healed.tobytes() == donor.tobytes()  # BIT identity
    # optimizer state: exact on the raw wire, bf16-rounded under bf16
    donor_leaves = jax.tree_util.tree_leaves(state["user"]["opt_state"])
    healed_leaves = jax.tree_util.tree_leaves(out["user"]["opt_state"])
    assert len(donor_leaves) == len(healed_leaves)
    import ml_dtypes

    for d, h in zip(donor_leaves, healed_leaves):
        d = np.asarray(d)
        h = np.asarray(h)
        assert h.dtype == d.dtype
        if wire == "bf16" and d.dtype == np.dtype(np.float32):
            expected = d.astype(ml_dtypes.bfloat16).astype(np.float32)
            np.testing.assert_array_equal(h, expected)
        else:
            assert h.tobytes() == d.tobytes()
    # a leaf outside params AND opt_state ships raw on EVERY wire:
    # protect-by-default, never silent rounding of maybe-weights
    assert (
        np.asarray(out["user"]["ema_weights"]).tobytes()
        == np.asarray(state["user"]["ema_weights"]).tobytes()
    )
    # skeleton-borne non-array leaves survive untouched
    assert out["torchft"] == {"step": 17, "batches_committed": 51}


def test_stream_heal_donor_never_pickles_bulk(server, monkeypatch):
    """The zero-copy contract on the donor: serving a streamed heal must
    not serialize the state dict (no per-request pickle, no full-payload
    cache) — only the small skeleton meta is pickled."""
    from torchft_tpu import checkpointing as C

    def boom(_):
        raise AssertionError(
            "serialize_state_dict used on the streamed heal path"
        )

    monkeypatch.setattr(C, "serialize_state_dict", boom)
    state = _donor_state()
    server.send_checkpoint([1], step=4, state_dict=state,
                           timeout=timedelta(seconds=10))
    out = server.recv_checkpoint(
        0, server.metadata(), 4, timeout=timedelta(seconds=10)
    )
    assert server.last_fetch_stats["path"] == "stream"
    assert server.last_fetch_stats["bytes"] > 0
    np.testing.assert_array_equal(
        np.asarray(out["user"]["params"]["dense"]),
        np.asarray(state["user"]["params"]["dense"]),
    )


def test_stream_heal_wrong_step_is_an_error(server):
    server.send_checkpoint([1], step=3, state_dict=_donor_state(),
                           timeout=timedelta(seconds=10))
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        CheckpointServer._fetch(
            f"{server.address()}5", timeout=timedelta(seconds=10)
        )
    assert exc_info.value.code == 400


def test_stream_heal_env_knobs(server, monkeypatch):
    """TORCHFT_HEAL_WIRE / TORCHFT_HEAL_STREAMS select the default wire
    and stream depth for recv_checkpoint (the manager heal path)."""
    monkeypatch.setenv("TORCHFT_HEAL_WIRE", "bf16")
    monkeypatch.setenv("TORCHFT_HEAL_STREAMS", "3")
    state = _donor_state()
    server.send_checkpoint([1], step=11, state_dict=state,
                           timeout=timedelta(seconds=10))
    out = server.recv_checkpoint(
        0, server.metadata(), 11, timeout=timedelta(seconds=10)
    )
    stats = server.last_fetch_stats
    assert stats["path"] == "stream"
    assert stats["wire"] == "bf16" and stats["streams"] == 3
    # params still bit-identical under the env-selected bf16 wire
    assert (
        np.asarray(out["user"]["params"]["bias"]).tobytes()
        == np.asarray(state["user"]["params"]["bias"]).tobytes()
    )


def test_stream_stale_publish_rejected(server):
    """A range request carrying the nonce of a SUPERSEDED publish must
    400, even at the same step: serving it from the new staging would
    hand a straggler-striped reader a torn mix of two checkpoints."""
    import urllib.request

    from torchft_tpu import checkpointing as C

    s1 = {"w": np.ones(256, np.float32)}
    server.send_checkpoint([1], step=6, state_dict=s1,
                           timeout=timedelta(seconds=10))
    with urllib.request.urlopen(
        f"{server.address()}6/streammeta/none", timeout=10
    ) as f:
        seq = C._SafeUnpickler(f).load()["seq"]
    # range with the live nonce serves
    with urllib.request.urlopen(
        f"{server.address()}6/stream/0/2/none/{seq}", timeout=10
    ) as f:
        assert len(f.read()) == 512  # half of 256 f32
    # republish at the SAME step
    server.disallow_checkpoint()
    server.send_checkpoint([1], step=6,
                           state_dict={"w": np.zeros(256, np.float32)},
                           timeout=timedelta(seconds=10))
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(
            f"{server.address()}6/stream/0/2/none/{seq}", timeout=10
        )
    assert exc_info.value.code == 400
    # a fresh fetch (meta + ranges under the new nonce) heals fine
    out = CheckpointServer.load_from_address(
        f"{server.address()}6", timeout=timedelta(seconds=10)
    )
    np.testing.assert_array_equal(
        np.asarray(out["w"]), np.zeros(256, np.float32)
    )


def test_stream_disallow_clears_staging_and_regates(server):
    """disallow_checkpoint must invalidate the stream staging (it aliases
    the live buffers) and re-gate the endpoints."""
    state = _donor_state()
    server.send_checkpoint([1], step=1, state_dict=state,
                           timeout=timedelta(seconds=10))
    CheckpointServer.load_from_address(
        f"{server.address()}1", timeout=timedelta(seconds=10)
    )
    assert server._stagings  # staging was built
    server.disallow_checkpoint()
    assert not server._stagings
    fast = CheckpointServer(timeout=timedelta(milliseconds=200))
    try:
        fast.send_checkpoint([1], 1, {"x": np.ones(4, np.float32)},
                             timeout=timedelta(seconds=5))
        fast.disallow_checkpoint()
        with pytest.raises(Exception):
            fast.recv_checkpoint(
                0, fast.metadata(), 1, timeout=timedelta(seconds=5)
            )
    finally:
        fast.shutdown()


# -- heal-stream fault fallback (the PR 5 contract under injected faults) ----
# Previously only timeout exhaustion was exercised; these cover the torn
# donor responses the chaos plane injects: a TRUNCATED range body and a
# mid-range CONNECTION RESET. Contract: the receiver cancels its
# surviving range readers and falls back to the pickled paths WITHOUT
# double-counting the timeout budget, and the healed bytes are exact.


def _proxy_for(server):
    import urllib.parse

    from torchft_tpu.chaos import HealFaultProxy

    parts = urllib.parse.urlparse(server.address())
    proxy = HealFaultProxy(f"{parts.scheme}://{parts.netloc}")
    return proxy, proxy.address() + parts.path


@pytest.mark.parametrize("mode", ["truncate_body", "reset_mid_range"])
def test_stream_fault_falls_back_within_budget(server, mode):
    import time

    state = {"params": {"w": np.arange(65536, dtype=np.float32)}}
    server.send_checkpoint([1], step=2, state_dict=state,
                           timeout=timedelta(seconds=10))
    proxy, addr = _proxy_for(server)
    try:
        proxy.mode = mode
        proxy.only_paths = ("/stream/",)
        proxy.max_faults = 1
        budget = timedelta(seconds=10)
        t0 = time.monotonic()
        out, stats = CheckpointServer._fetch(
            addr + "2", timeout=budget, streams=4
        )
        wall = time.monotonic() - t0
        assert proxy.faults_fired == 1
        # fell back off the stream path; data exact
        assert stats["path"] != "stream"
        np.testing.assert_array_equal(
            out["params"]["w"], state["params"]["w"]
        )
        # no budget double-counting: a torn response fails FAST (the
        # range reader sees a short read/reset immediately), so the
        # whole heal — stream attempt + fallback — stays well inside
        # ONE budget, not stacked fresh budgets per fallback tier
        assert wall < budget.total_seconds(), wall
    finally:
        proxy.shutdown()


def test_stream_fault_cancels_surviving_readers(server):
    """After a torn range kills the stream fetch, the donor's in-flight
    reader count must drain promptly — the surviving range readers were
    CANCELLED, not left downloading against the fallback (which would
    pin the donor's next disallow_checkpoint)."""
    import time

    state = {"params": {"w": np.arange(1 << 18, dtype=np.float32)}}
    server.send_checkpoint([1], step=3, state_dict=state,
                           timeout=timedelta(seconds=10))
    proxy, addr = _proxy_for(server)
    try:
        proxy.mode = "reset_mid_range"
        proxy.only_paths = ("/stream/",)
        proxy.max_faults = 1
        out, _stats = CheckpointServer._fetch(
            addr + "3", timeout=timedelta(seconds=10), streams=4
        )
        np.testing.assert_array_equal(
            out["params"]["w"], state["params"]["w"]
        )
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:  # deadline-bounded poll
            with server._stream_cv:
                if server._stream_inflight == 0:
                    break
            time.sleep(0.05)
        assert server._stream_inflight == 0
    finally:
        proxy.shutdown()


# ---------------------------------------------------------------------------
# Range-limited capture (the durable tier's shard_of= discipline)
# ---------------------------------------------------------------------------


def _shard_state():
    # Odd sizes chosen so W=3 floor-split boundaries land mid-leaf AND
    # mid-element on the bf16 wire (opt_state halves its byte width).
    return {
        "params": {
            "w": np.arange(1001, dtype=np.float32),
            "b": np.arange(7, dtype=np.float32) * 0.5,
        },
        "opt_state": {
            "m": np.arange(503, dtype=np.float32) * 0.25,
            "v": np.arange(129, dtype=np.float32) * 4.0,
        },
        "step": 9,
    }


@pytest.mark.parametrize("wire", [None, "bf16"])
@pytest.mark.parametrize("world", [1, 2, 3, 5])
def test_range_capture_reassembles_full_stream(wire, world):
    """Concatenating every member's shard_of capture over its
    shard_bounds span must be byte-identical to the unsharded stream —
    straddling leaves contribute exactly their in-range element slice,
    with the wire-itemsize outward alignment covering split elements."""
    import io

    from torchft_tpu.checkpointing import _StreamStaging
    from torchft_tpu.durable import shard_bounds

    state = _shard_state()
    full = _StreamStaging(state, wire, snapshot=True)
    buf = io.BytesIO()
    full.write_range(buf, 0, full.total)
    want = buf.getvalue()
    assert len(want) == full.total

    got = b""
    for rank in range(world):
        bounds = shard_bounds(full.total, world)
        begin, end = bounds[rank], bounds[rank + 1]
        st = _StreamStaging(
            state, wire, snapshot=True, shard_of=(rank, world)
        )
        assert st.total == full.total  # layout is shard-blind
        b = io.BytesIO()
        st.write_range(b, begin, end)
        piece = b.getvalue()
        assert len(piece) == end - begin
        # capture cost is the member's span plus at most one wire
        # element of outward alignment per straddled boundary (params
        # stay f32 even on the bf16 wire, so the element is <= 4 bytes)
        assert st.captured_bytes <= (end - begin) + 2 * 4
        assert st.range_crc32c(begin, end) == full.range_crc32c(begin, end)
        got += piece
    assert got == want


def test_range_capture_out_of_span_read_raises():
    """A shard-limited staging must refuse reads outside its captured
    span — a silent zero-fill or a bisect wrap to the wrong segment
    would ship a torn shard that still CRCs clean."""
    import io

    from torchft_tpu.checkpointing import _StreamStaging
    from torchft_tpu.durable import shard_bounds

    state = _shard_state()
    probe = _StreamStaging(state, None, snapshot=True)
    bounds = shard_bounds(probe.total, 3)
    begin, end = bounds[1], bounds[2]
    st = _StreamStaging(state, None, snapshot=True, shard_of=(1, 3))
    for bad in [(0, end), (begin, probe.total), (begin - 1, end)]:
        with pytest.raises(ValueError, match="outside captured span"):
            st.write_range(io.BytesIO(), *bad)
        with pytest.raises(ValueError, match="outside captured span"):
            st.range_crc32c(*bad)
    # the span itself stays servable after the failed reads
    b = io.BytesIO()
    st.write_range(b, begin, end)
    assert len(b.getvalue()) == end - begin


def test_range_capture_pinned_defers_wire_cast():
    """pin_leaves=True with jax leaves: capture stores views + deferred
    (slice, wdtype) casts, and the writer-side _seg() resolution yields
    bytes identical to the eager-copy capture."""
    import io

    import jax.numpy as jnp

    from torchft_tpu.checkpointing import _StreamStaging
    from torchft_tpu.durable import shard_bounds

    state = {
        "params": {"w": jnp.arange(257, dtype=jnp.float32)},
        "opt_state": {"m": jnp.arange(130, dtype=jnp.float32) * 0.5},
    }
    eager = _StreamStaging(state, "bf16", snapshot=True, shard_of=(0, 2))
    pinned = _StreamStaging(
        state, "bf16", snapshot=True, shard_of=(0, 2), pin_leaves=True
    )
    assert pinned._pins  # jax leaves really were pinned, not copied
    begin, end = shard_bounds(eager.total, 2)[:2]
    be, bp = io.BytesIO(), io.BytesIO()
    eager.write_range(be, begin, end)
    pinned.write_range(bp, begin, end)
    assert be.getvalue() == bp.getvalue()
    assert pinned.range_crc32c(begin, end) == eager.range_crc32c(begin, end)


# -- the state transfer on the span primitive ------------------------------


def _host_events(logdir, body):
    """``body`` under a CPU capture: the ``torchft::*`` and ``test::*``
    host events as (thread line, name, start_ns, end_ns, stats)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(logdir), "plugins", "profile", "*", "*.xplane.pb")
    )
    return [
        (i, e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for i, line in enumerate(plane.lines)
        for e in line.events
        if e.name.startswith(("torchft::", "test::"))
    ]


def test_streamed_fetch_phases_nest_under_heal_fetch(tmp_path):
    """``meta_s``, ``fetch_s`` and ``h2d_s`` are phases of the healer's
    ``torchft::heal_fetch``, on its thread, with the owner's step; the
    donor's staging and each range it serves are spans under
    ``torchft::send_checkpoint``'s name on the SERVING threads, filed in
    the donor's timers ``send_stage`` / ``send_serve`` and its counter
    ``send_bytes``, which matches the bytes fetched."""
    from torchft_tpu.metrics import Metrics

    donor = CheckpointServer(timeout=timedelta(seconds=10))
    healer = CheckpointServer(timeout=timedelta(seconds=10))
    # what a Manager does with the transport it owns
    donor.metrics, healer.metrics = Metrics(), Metrics()
    donor.metrics.step, healer.metrics.step = 21, 0
    try:
        def body():
            donor.send_checkpoint([1], step=21, state_dict=_donor_state(),
                                  timeout=timedelta(seconds=10))
            with healer.metrics.timed("heal_fetch"):
                healer.recv_checkpoint(
                    0, donor.metadata(), 21, timeout=timedelta(seconds=10)
                )

        events = _host_events(tmp_path, body)
    finally:
        donor.shutdown()
        healer.shutdown()

    stats = healer.last_fetch_stats
    assert stats["path"] == "stream"
    assert {"meta_s", "fetch_s", "h2d_s"} <= set(stats)
    assert 0 < stats["meta_s"] < stats["fetch_s"]
    (whole,) = [e for e in events if e[1] == "torchft::heal_fetch"]
    by_name = {}
    for name in ("meta", "stream", "h2d"):
        (by_name[name],) = [
            e for e in events if e[1] == f"torchft::heal_fetch/{name}"
        ]
    for e in by_name.values():
        assert e[0] == whole[0] and whole[2] <= e[2] and e[3] <= whole[3]
        assert e[4] == {"step": 0}
    meta, stream, h2d = (by_name[n] for n in ("meta", "stream", "h2d"))
    assert meta[3] <= stream[2] and stream[3] <= h2d[2]
    ns = 1e9
    assert stats["meta_s"] == pytest.approx((meta[3] - meta[2]) / ns, abs=5e-3)
    # fetch_s is what it was: the layout fetch to the last byte
    assert stats["fetch_s"] == pytest.approx((stream[3] - meta[2]) / ns, abs=5e-3)
    assert stats["h2d_s"] == pytest.approx((h2d[3] - h2d[2]) / ns, abs=5e-3)

    # the donor: one staging, one span a range, bytes that add up
    snap = donor.metrics.snapshot()
    assert snap["timers_s"]["send_stage"]["n"] == 1
    assert snap["timers_s"]["send_serve"]["n"] == stats["streams"]
    assert snap["counters"]["send_bytes"] == stats["bytes"]
    (stage,) = [e for e in events if e[1] == "torchft::send_checkpoint/stage"]
    serves = [e for e in events if e[1] == "torchft::send_checkpoint/serve"]
    assert stage[4] == {"step": 21} and stage[0] != whole[0]
    assert len(serves) == stats["streams"]
    assert all(e[4]["step"] == 21 for e in serves)
    assert sum(e[4]["bytes"] for e in serves) == stats["bytes"]
    assert snap["timers_s"]["send_stage"]["total_s"] == pytest.approx(
        (stage[3] - stage[2]) / ns, abs=5e-3
    )
    # the healer served nothing
    assert "send_serve" not in healer.metrics.snapshot()["timers_s"]


@pytest.mark.parametrize("path", ["striped", "single"])
def test_pickled_fallbacks_time_their_fetch_as_a_phase(server, tmp_path, monkeypatch, path):
    """Against a peer without the stream endpoint the fetch that
    succeeds is the phase ``torchft::heal_fetch/<path>``, and ``fetch_s``
    is its seconds."""
    def no_stream(*a, **k):
        raise urllib.error.HTTPError("u", 404, "no stream endpoint", {}, None)

    monkeypatch.setattr(CheckpointServer, "_load_stream", no_stream)
    server.send_checkpoint([1], step=2, state_dict={"w": np.ones(64, np.float32)},
                           timeout=timedelta(seconds=10))
    got = {}

    def body():
        got["out"], got["stats"] = CheckpointServer._fetch(
            f"{server.address()}2", timeout=timedelta(seconds=10),
            stripes=4 if path == "striped" else 1, step=5,
        )

    events = _host_events(tmp_path, body)
    assert got["stats"]["path"] == path
    (phase,) = [e for e in events if e[1] == f"torchft::heal_fetch/{path}"]
    assert phase[4] == {"step": 5}
    assert got["stats"]["fetch_s"] == pytest.approx(
        (phase[3] - phase[2]) / 1e9, abs=5e-3
    )
    np.testing.assert_array_equal(got["out"]["w"], np.ones(64, np.float32))
