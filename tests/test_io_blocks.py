"""I/O block tests: file roundtrip, TCP pipe, seify dummy driver, ctrl port REST.

Reference: `tests/seify.rs` (dummy driver), `tests/channel_source.rs`, ctrl_port routes.
"""

import asyncio
import threading

import numpy as np
import pytest

from futuresdr_tpu import Flowgraph, Runtime, Pmt
from futuresdr_tpu.blocks import (FileSource, FileSink, VectorSource, VectorSink, Head,
                                  SeifySource, SeifySink, SeifyBuilder, TcpSink, TcpSource,
                                  ChannelSource, ChannelSink, NullSink)


def test_file_roundtrip(tmp_path):
    path = str(tmp_path / "samples.bin")
    data = np.random.default_rng(0).standard_normal(10_000).astype(np.float32)
    fg = Flowgraph()
    fg.connect(VectorSource(data), FileSink(path, np.float32))
    Runtime().run(fg)

    fg2 = Flowgraph()
    src = FileSource(path, np.float32)
    snk = VectorSink(np.float32)
    fg2.connect(src, snk)
    Runtime().run(fg2)
    np.testing.assert_array_equal(snk.items(), data)


def test_seify_dummy_source():
    fg = Flowgraph()
    src = SeifyBuilder().args("driver=dummy,throttle=false").sample_rate(1e6).build_source()
    head = Head(np.complex64, 50_000)
    snk = VectorSink(np.complex64)
    fg.connect(src, head, snk)
    Runtime().run(fg)
    x = snk.items()
    assert len(x) == 50_000
    # dummy driver: tone at 10% of fs dominates
    spec = np.abs(np.fft.fft(x[:16384] * np.hanning(16384)))
    assert abs(np.fft.fftfreq(16384)[np.argmax(spec)] - 0.1) < 0.01


def test_seify_sink_and_handlers():
    fg = Flowgraph()
    src = ChannelSource(np.complex64)
    snk = SeifySink("driver=dummy")
    fg.connect(src, snk)
    rt = Runtime()
    running = rt.start(fg)
    rt.scheduler.run_coro_sync(src.queue.put(np.zeros(10_000, np.complex64)))
    r = rt.scheduler.run_coro_sync(running.handle.call(snk, "freq", Pmt.f64(433e6)))
    assert r == Pmt.ok()
    rt.scheduler.run_coro_sync(src.queue.put(None))   # EOS after the call landed
    running.wait_sync()
    assert snk.device.driver.tx_written == 10_000
    assert snk.device.driver.frequency == 433e6


def test_file_driver_replay(tmp_path):
    """driver=file replays an IQ recording through the seify source (file-trx role)."""
    path = str(tmp_path / "iq.c64")
    data = np.exp(1j * 2 * np.pi * 0.05 * np.arange(5000)).astype(np.complex64)
    data.tofile(path)
    fg = Flowgraph()
    src = SeifySource(f"driver=file,path={path},throttle=false,repeat=true")
    head = Head(np.complex64, 12_000)
    snk = VectorSink(np.complex64)
    fg.connect(src, head, snk)
    Runtime().run(fg)
    got = snk.items()
    assert len(got) == 12_000
    np.testing.assert_array_equal(got[:5000], data)
    np.testing.assert_array_equal(got[5000:10000], data)   # looped


def test_seify_cmd_config_map():
    fg = Flowgraph()
    src = SeifySource("driver=dummy,throttle=false")
    snk = NullSink(np.complex64)
    # no Head: the flowgraph must outlive the cmd call below (1000 samples
    # could finish before it lands); stop_sync() ends the run
    fg.connect(src, snk)
    rt = Runtime()
    running = rt.start(fg)
    r = rt.scheduler.run_coro_sync(running.handle.call(
        src, "cmd", Pmt.map({"freq": 94.2e6, "gain": 30.0})))
    assert r == Pmt.ok()
    running.stop_sync()
    assert src.device.driver.frequency == 94.2e6
    assert src.device.driver.gain == 30.0


def test_tcp_pipe():
    port = 28712
    data = np.arange(20_000, dtype=np.float32)

    fg_rx = Flowgraph()
    tsrc = TcpSource("127.0.0.1", port, np.float32, listen=True)
    rsnk = VectorSink(np.float32)
    fg_rx.connect(tsrc, rsnk)
    rt_rx = Runtime()
    running_rx = rt_rx.start(fg_rx)

    fg_tx = Flowgraph()
    fg_tx.connect(VectorSource(data), TcpSink("127.0.0.1", port, np.float32))
    Runtime().run(fg_tx)

    running_rx.wait_sync()
    np.testing.assert_array_equal(rsnk.items(), data)


def test_channel_source_sink():
    q_in = None
    fg = Flowgraph()
    src = ChannelSource(np.float32)
    snk = ChannelSink(np.float32)
    fg.connect(src, snk)
    rt = Runtime()
    running = rt.start(fg)

    async def feed():
        await src.queue.put(np.arange(100, dtype=np.float32))
        await src.queue.put(np.arange(100, 200, dtype=np.float32))
        await src.queue.put(None)

    rt.scheduler.run_coro_sync(feed())
    running.wait_sync()

    chunks = []
    async def drain():
        while True:
            c = snk.queue.get_nowait()
            if c is None:
                return
            chunks.append(c)

    rt.scheduler.run_coro_sync(drain())
    np.testing.assert_array_equal(np.concatenate(chunks), np.arange(200, dtype=np.float32))


def test_ctrl_port_rest_roundtrip():
    """Full REST path: list → describe → call handler (reference ctrl_port routes)."""
    import json
    import urllib.request

    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    from futuresdr_tpu.blocks import SignalSource

    fg = Flowgraph()
    src = SignalSource("complex", 1000.0, 48000.0)
    head = Head(np.complex64, 10_000_000)
    snk = NullSink(np.complex64)
    fg.connect(src, head, snk)
    rt = Runtime()
    cp = ControlPort(rt.handle, bind="127.0.0.1:29317")
    cp.start()
    running = rt.start(fg)
    try:
        base = "http://127.0.0.1:29317"
        ids = json.load(urllib.request.urlopen(f"{base}/api/fg/"))
        assert ids == [0]
        desc = json.load(urllib.request.urlopen(f"{base}/api/fg/0/"))
        assert len(desc["blocks"]) == 3
        b0 = json.load(urllib.request.urlopen(f"{base}/api/fg/0/block/0/"))
        assert b0["type_name"] == "SignalSource"
        req = urllib.request.Request(
            f"{base}/api/fg/0/block/0/call/freq/",
            data=json.dumps({"F64": 2000.0}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        r = json.load(urllib.request.urlopen(req))
        assert r == "Ok"
        # remote client speaks the same API
        from futuresdr_tpu.ctrl import Remote

        async def via_client():
            rfg = await Remote(base).flowgraph(0)
            blk = await rfg.block(0)
            assert "freq" in blk.handlers()          # typed handler enumeration
            conns = await rfg.connections()
            assert any(c.kind == "stream" for c in conns)
            return await blk.callback("freq", Pmt.f64(3000.0))

        res = rt.scheduler.run_coro_sync(via_client())
        assert res == Pmt.ok()
    finally:
        running.stop_sync()
        cp.stop()
