"""Profiler statistics engine (paddle_tpu/profiler/stats).

Reference role: python/paddle/profiler/profiler_statistic.py (summary
tables, gen_layer_flops) + paddle/fluid/platform/profiler/mem_tracing.h
(memory-event tracing). Covers:

- summary-table correctness on a known synthetic 3-op trace,
- analytic-FLOPs parity against hand-computed matmul/attention counts
  (registry formulas AND the counts the dispatch hook books on real ops),
- memory peak/live monotonicity across profiled steps,
- the acceptance run: a real profiled GPT train loop whose summary()
  prints per-op and per-layer tables (time + calls + FLOPs + MFU) and the
  per-step HBM peak/live report.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu import profiler as prof
from paddle_tpu.core import dispatch
from paddle_tpu.profiler import stats as pstats
from paddle_tpu.profiler.stats import aggregator


def _op(name, dur, flops, layer, cat="Operator"):
    return {"name": name, "ph": "X", "cat": cat, "ts": 0.0, "dur": dur,
            "args": {"flops": flops, "layer": layer}}


def _fwd(path, dur):
    return {"name": path, "ph": "X", "cat": "Forward", "ts": 0.0,
            "dur": dur}


class _FakeProf:
    """Minimal Profiler stand-in for rendering tests."""

    def __init__(self, events, step_records=()):
        self._evs = events
        self.step_records = list(step_records)
        self._jax_dir = None
        self._session = None

    def events(self):
        return list(self._evs)


class TestKnownTrace:
    """Summary-table correctness on a hand-built 3-op trace."""

    EVENTS = [
        _op("matmul", 100.0, 1000, "net.fc1"),
        _op("matmul", 300.0, 1000, "net.fc2"),
        _op("relu", 50.0, 10, "net"),
        _fwd("net", 500.0),
        _fwd("net.fc1", 150.0),
        _fwd("net.fc2", 320.0),
    ]

    def test_op_stats(self):
        ops = aggregator.op_stats(self.EVENTS)
        assert set(ops) == {"matmul", "relu"}
        mm = ops["matmul"]
        assert mm.calls == 2
        assert mm.total == pytest.approx(400.0)
        assert mm.avg == pytest.approx(200.0)
        assert mm.max == pytest.approx(300.0)
        assert mm.min == pytest.approx(100.0)
        assert mm.flops == 2000
        assert ops["relu"].calls == 1
        assert ops["relu"].flops == 10

    def test_layer_rollup(self):
        layers = aggregator.layer_stats(self.EVENTS)
        assert set(layers) == {"net", "net.fc1", "net.fc2"}
        # the root rolls up every op dispatched under its prefix
        assert layers["net"].flops == 2010
        assert layers["net.fc1"].flops == 1000
        assert layers["net.fc2"].flops == 1000
        assert layers["net"].total == pytest.approx(500.0)

    def test_rendered_tables(self):
        p = _FakeProf(self.EVENTS, step_records=[
            {"step": 1, "time_ms": 0.45, "flops": 2010,
             "flops_per_sec": 2010 / 0.45e-3, "mfu": 0.1}])
        text = pstats.build_summary(p)
        assert "Operator Summary" in text
        assert "Layer Summary" in text
        assert "Step Summary" in text
        for col in ("Calls", "Total", "Avg", "Max", "Min", "FLOPs", "MFU"):
            assert col in text
        assert "matmul" in text and "net.fc1" in text
        d = pstats.build_summary_dict(p, top_ops=2)
        assert d["steps"] == 1
        assert d["flops_per_step"] == 2010
        assert d["top_ops"][0]["name"] == "matmul"
        assert d["top_ops"][0]["calls"] == 2


class TestNameStack:
    def test_layerlist_setitem_and_insert_requalify(self):
        net = nn.Layer()
        net.blocks = nn.LayerList([nn.Linear(2, 2), nn.Linear(2, 2)])
        assert net.blocks[0].__dict__["_local_name"] == "blocks.0"
        net.blocks[1] = nn.Linear(2, 2)
        assert net.blocks[1].__dict__["_local_name"] == "blocks.1"
        net.blocks.insert(0, nn.Linear(2, 2))
        # shifted indices must refresh every child's segment
        assert [b.__dict__["_local_name"] for b in net.blocks] == \
            ["blocks.0", "blocks.1", "blocks.2"]
        net.blocks.append(nn.Linear(2, 2))
        assert net.blocks[3].__dict__["_local_name"] == "blocks.3"


class TestFlopsParity:
    """Analytic formulas vs hand-computed counts."""

    def test_matmul_formula(self):
        x = np.zeros((4, 8), np.float32)
        y = np.zeros((8, 16), np.float32)
        out = np.zeros((4, 16), np.float32)
        # [4,8] @ [8,16]: 2*M*N*K = 2*4*16*8
        assert dispatch.flops_for("matmul", [x, y], [out], {}) == 1024
        # transpose_x: x is [K, M]
        xt = np.zeros((8, 4), np.float32)
        assert dispatch.flops_for(
            "matmul", [xt, y], [out], {"transpose_x": True}) == 1024

    def test_attention_formula(self):
        b, l, h, d = 2, 16, 4, 8
        q = np.zeros((b, l, h, d), np.float32)
        out = np.zeros((b, l, h, d), np.float32)
        full = dispatch.flops_for(
            "scaled_dot_product_attention", [q, q, q], [out], {})
        # QK^T + PV: 2 * (2*B*H*L*S*D)
        assert full == 4 * b * h * l * l * d == 65536
        causal = dispatch.flops_for(
            "scaled_dot_product_attention", [q, q, q], [out],
            {"is_causal": True})
        assert causal == full // 2

    def test_elementwise_default_and_failure(self):
        out = np.zeros((3, 5), np.float32)
        # no registry entry -> one FLOP per output element
        assert dispatch.flops_for("someramp", [out], [out], {}) == 15
        # formula failure must yield 0, never raise
        assert dispatch.flops_for("matmul", [object()], [out], {}) == 0

    def test_real_dispatch_books_hand_computed_flops(self):
        """The dispatch hook attaches the analytic count to each op
        event: check matmul and causal attention on real tensors."""
        x = paddle.to_tensor(np.random.rand(4, 8).astype("float32"))
        y = paddle.to_tensor(np.random.rand(8, 16).astype("float32"))
        q = paddle.to_tensor(np.random.rand(2, 16, 4, 8).astype("float32"))
        p = prof.Profiler(timer_only=True, with_flops=True)
        p.start()
        try:
            paddle.matmul(x, y)
            nn.functional.scaled_dot_product_attention(
                q, q, q, is_causal=True)
        finally:
            p.stop()
        ops = aggregator.op_stats(p.events())
        assert ops["matmul"].flops == 2 * 4 * 16 * 8
        att = ops["scaled_dot_product_attention"]
        assert att.flops == 4 * 2 * 4 * 16 * 16 * 8 // 2

    def test_hook_removed_after_stop(self):
        assert dispatch._PROFILE_HOOK is None


class TestMemoryTracer:
    def test_explicit_events_and_monotone_peak(self):
        from paddle_tpu import device

        p = prof.Profiler(timer_only=True, profile_memory=True)
        p.start()
        try:
            keep = []
            for i in range(4):
                device.record_memory_event("test_alloc", 1 << 20)
                keep.append(paddle.to_tensor(
                    np.zeros((64, 64), np.float32)))
                p.step()
        finally:
            p.stop()
        mem = p._session.memory
        kinds = {e["kind"] for e in mem.alloc_events}
        assert "test_alloc" in kinds
        steps = mem.steps
        assert len(steps) == 4
        peaks = [r["peak_bytes"] for r in steps]
        assert peaks == sorted(peaks), "per-step peak must be monotone"
        assert all(r["peak_bytes"] >= r["live_bytes"] >= 0 for r in steps)
        # alloc-event counter is cumulative, hence monotone too
        counts = [r["alloc_events"] for r in steps]
        assert counts == sorted(counts) and counts[-1] >= 4

    def test_memory_hook_removed_after_stop(self):
        from paddle_tpu import device

        assert device._MEM_HOOK is None


class TestProfiledGPT:
    """Acceptance run: profile a real (tiny) GPT train loop and check
    every summary section renders with real content."""

    @pytest.fixture(scope="class")
    def profiled(self):
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=32)
        model = GPTForCausalLM(cfg)
        model.train()
        lossf = nn.CrossEntropyLoss()

        def loss_fn(m, ids, labels):
            logits = m(ids)
            return lossf(logits.reshape([-1, cfg.vocab_size]),
                         labels.reshape([-1]))

        step = TrainStep(model, opt.AdamW(
            1e-4, parameters=model.parameters()), loss_fn)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (2, 8)).astype("int64")
        labels = np.roll(ids, -1, axis=1)

        p = prof.Profiler(timer_only=True, profile_memory=True,
                          with_flops=True)
        p.start()
        try:
            for _ in range(3):
                loss = step(ids, labels)
                float(loss.numpy())
                p.step()
        finally:
            p.stop()
        return p

    def test_summary_prints_all_sections(self, profiled, capsys):
        text = profiled.summary()
        assert capsys.readouterr().out.strip() != ""
        assert "Operator Summary" in text
        assert "Layer Summary" in text
        assert "Step Summary" in text
        assert "Memory Summary" in text
        assert "MFU" in text
        assert "buffer donation" in text

    def test_per_op_table_has_model_ops(self, profiled):
        ops = aggregator.op_stats(profiled.events())
        names = set(ops)
        assert "matmul" in names or "linear" in names
        assert "scaled_dot_product_attention" in names
        assert any(st.flops > 0 for st in ops.values())

    def test_per_layer_rollup_follows_name_stack(self, profiled):
        layers = aggregator.layer_stats(profiled.events())
        paths = set(layers)
        # the trace pass runs the model eagerly under Layer.__call__, so
        # the dotted name-stack paths of the block stack must appear
        assert any("blocks" in p for p in paths)
        assert any(".attn" in p or ".mlp" in p for p in paths)
        root = min(paths, key=len)
        assert layers[root].flops >= max(
            st.flops for st in layers.values()) > 0

    def test_step_series_flops_and_mfu(self, profiled):
        recs = profiled.step_records
        assert len(recs) == 3
        # every executed step books 3x the (identical) forward count
        assert len({r["flops"] for r in recs}) == 1
        assert all(r["flops"] > 0 for r in recs)
        assert all(r["time_ms"] > 0 for r in recs)
        # a CPU run has no MFU: no peak is assumed for a host
        assert all(r["mfu"] is None for r in recs)
        # forward analytic count must cover at least the block matmuls:
        # qkv + out + fc1 + fc2 per layer, tokens = 2*8
        cfg_h, tokens, layers_n = 32, 16, 2
        per_layer = 2 * tokens * (cfg_h * 3 * cfg_h + cfg_h * cfg_h +
                                  cfg_h * 4 * cfg_h + 4 * cfg_h * cfg_h)
        assert recs[0]["flops"] >= 3 * layers_n * per_layer

    def test_memory_series_monotone_peak(self, profiled):
        steps = profiled._session.memory.steps
        assert len(steps) == 3
        peaks = [r["peak_bytes"] for r in steps]
        assert peaks == sorted(peaks)
        assert peaks[-1] > 0
        don = profiled._session.memory.donation
        assert don is not None and don["params_bytes"] > 0

    def test_profiler_callback_drives_fit(self, capsys):
        """hapi ProfilerCallback: start/step/stop through Model.fit, one
        summary at train end."""
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import ProfilerCallback
        from paddle_tpu.io import TensorDataset

        paddle.seed(0)
        x = np.random.rand(16, 8).astype("float32")
        y = np.random.randint(0, 4, (16, 1)).astype("int64")
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        model = Model(net)
        model.prepare(opt.SGD(0.1, parameters=net.parameters()),
                      nn.CrossEntropyLoss())
        cb = ProfilerCallback()
        model.fit(TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)]),
                  batch_size=4, epochs=1, verbose=0, callbacks=[cb])
        capsys.readouterr()
        assert cb.last_summary is not None
        assert "Operator Summary" in cb.last_summary
        assert len(cb.profiler.step_records) == 4
        from paddle_tpu.core import dispatch as _d
        assert _d._PROFILE_HOOK is None  # uninstalled at train end

    def test_fit_exception_still_uninstalls_hooks(self):
        """A batch that raises must not leak the global dispatch/memory
        hooks (Model.fit runs on_train_end in a finally)."""
        from paddle_tpu.core import dispatch as _d
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import ProfilerCallback
        from paddle_tpu.io import TensorDataset

        x = paddle.to_tensor(np.random.rand(8, 4).astype("float32"))
        y = paddle.to_tensor(np.zeros((8, 1), np.int64))
        net = nn.Linear(4, 2)
        model = Model(net)
        model.prepare(opt.SGD(0.1, parameters=net.parameters()),
                      nn.CrossEntropyLoss())
        cb = ProfilerCallback(print_summary=False)
        boom = RuntimeError("boom")

        def raising_step(*a, **k):
            raise boom

        model._train_step = raising_step
        with pytest.raises(RuntimeError):
            model.fit(TensorDataset([x, y]), batch_size=4, epochs=1,
                      verbose=0, callbacks=[cb])
        assert _d._PROFILE_HOOK is None
        from paddle_tpu import device
        assert device._MEM_HOOK is None

    def test_summary_dict_digest(self, profiled):
        d = profiled.summary_dict(top_ops=5)
        assert d["steps"] == 3
        assert d["avg_step_time_ms"] > 0
        assert d["flops_per_step"] > 0
        assert d["device_peak_flops"] is None and "avg_mfu" not in d
        assert len(d["top_ops"]) == 5
        assert d["memory"]["peak_bytes"] > 0
        assert d["donation"]["params_bytes"] > 0


class TestDevicePeaks:
    """One table keyed by device_kind; no platform-keyed or CPU default."""

    def test_v5e_row(self):
        row = pstats.device_peaks("TPU v5 lite")
        assert row["bf16_flops"] == 197e12 and row["int8_ops"] == 393e12
        assert row["hbm_bytes_per_s"] == 819e9 and row["hbm_bytes"] == 16e9

    def test_unknown_kind_raises(self):
        with pytest.raises(LookupError, match="no published peaks"):
            pstats.device_peaks("TPU v99")
        # this host's own device kind ("cpu") is not in the table either
        with pytest.raises(LookupError):
            pstats.device_peaks()

    def test_cpu_has_no_peak_and_flag_overrides(self):
        assert pstats.device_peak_flops() is None
        paddle.set_flags({"FLAGS_device_peak_flops": 2e12})
        try:
            assert pstats.device_peak_flops() == 2e12
            p = prof.Profiler(timer_only=True)
            p.start()
            p.step()
            p.stop()
            assert p.step_records[0]["mfu"] == 0.0
        finally:
            paddle.set_flags({"FLAGS_device_peak_flops": 0.0})
