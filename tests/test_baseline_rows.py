"""The BASELINE.md single-chip rows, verbatim (the driver's north-star
table): ResNet-50 on CIFAR-shaped data trains end-to-end in DYGRAPH
mode, and BERT-base-style MLM trains under bf16 AMP O2. On the CI host
these run at CPU-tractable sizes."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu import amp


class TestResNetCifarDygraph:
    """BASELINE row: 'ResNet-50 / CIFAR-10 | trains end-to-end, loss
    parity | 1 TPU chip | dygraph, set_device'."""

    def _train(self, model, steps=4, batch=8, lr=0.01):
        o = opt.Momentum(lr, parameters=model.parameters())
        lossf = nn.CrossEntropyLoss()
        rng = np.random.RandomState(0)
        X = rng.randn(batch, 3, 32, 32).astype("float32")
        Y = rng.randint(0, 10, (batch,)).astype("int64")
        losses = []
        for _ in range(steps):
            loss = lossf(model(paddle.to_tensor(X)), paddle.to_tensor(Y))
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss.numpy()))
        return losses

    def test_resnet50_cifar_dygraph_loss_decreases(self):
        """The literal baseline row (Bottleneck resnet50), cut to what the
        assertion needs: two eager steps on one tiny batch."""
        from paddle_tpu.models import resnet50

        paddle.seed(0)
        losses = self._train(resnet50(num_classes=10, small_input=True),
                             steps=2, batch=2, lr=0.003)
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses


class TestBertMlmAmpO2:
    """BASELINE row: 'BERT-base MLM, bf16 AMP (O2) | trains end-to-end |
    1 TPU chip | paddle.amp-equivalent autocast'."""

    def test_bert_mlm_bf16_o2_trains(self):
        from paddle_tpu.models import BertConfig, BertForMaskedLM

        cfg = BertConfig(vocab_size=256, hidden_size=64, num_layers=2,
                         num_heads=4, intermediate_size=128,
                         max_position=64)
        paddle.seed(0)
        model = BertForMaskedLM(cfg)
        model = amp.decorate(model, level="O2", dtype="bfloat16")
        o = opt.AdamW(5e-3, parameters=model.parameters(),
                      multi_precision=True)
        # params really are bf16 with fp32 master weights in the optimizer
        p0 = next(iter(model.parameters()))
        assert "bfloat16" in str(p0.dtype)

        rng = np.random.RandomState(0)
        MASK = 1

        def make_batch():
            ids = rng.randint(4, cfg.vocab_size, (4, 32)).astype("int64")
            masked = ids.copy()
            mask_pos = rng.rand(*ids.shape) < 0.15
            mask_pos[:, 0] = True  # at least one masked position per row
            masked[mask_pos] = MASK  # MLM corruption
            labels = np.where(mask_pos, ids, -100)  # TRUE MLM objective:
            # loss only at masked positions (ignore_index) — copy-through
            # of visible tokens cannot satisfy this test
            return paddle.to_tensor(masked), paddle.to_tensor(labels)

        def probe_loss(batch):
            with paddle.no_grad(), amp.auto_cast(enable=True,
                                                 dtype="bfloat16"):
                return float(model.loss(*batch).numpy())

        probe = make_batch()       # FIXED held-out batch
        before = probe_loss(probe)
        losses = []
        for _ in range(6):
            with amp.auto_cast(enable=True, dtype="bfloat16"):
                loss = model.loss(*make_batch())
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss.numpy()))
        after = probe_loss(probe)
        assert all(np.isfinite(losses)), losses
        # the fixed probe batch's loss must improve after training (the
        # model learns copy-through + token marginals even on random data)
        assert after < before, (before, after)
