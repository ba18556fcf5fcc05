"""End-to-end SSD-lite detection: matching, loss descent
on the voc2012 reader, and above-chance mAP via DetectionMAP
(reference layers/detection.py ssd_loss / detection_output +
metrics.py:566)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.executor import Trainer
from paddle_tpu.data.datasets import voc2012_train
from paddle_tpu.metrics import DetectionMAP
from paddle_tpu.models.detection import (SSDLite, ssd_detect, ssd_loss,
                                         ssd_match)
from paddle_tpu.optim.optimizer import Adam

IMG = 96
NCLS = 4


def _batches(bs=8, n=None):
    rows = list(voc2012_train(image_size=IMG, num_classes=NCLS,
                              max_boxes=4, synthetic_n=64)())
    out = []
    for i in range(0, len(rows) - bs + 1, bs):
        chunk = rows[i:i + bs]
        out.append(tuple(np.stack([r[j] for r in chunk])
                         for j in range(4)))
        if n and len(out) >= n:
            break
    return out


def test_ssd_match_exact_prior():
    model = SSDLite(num_classes=NCLS, image_size=IMG)
    priors, _ = model.priors()
    # ground truth exactly equal to some prior must match it as positive
    gt = priors[100:101]
    conf_t, loc_t, pos = ssd_match(priors, jnp.concatenate(
        [gt, jnp.zeros((3, 4))]), jnp.asarray([2, 0, 0, 0]),
        jnp.asarray(1))
    assert bool(pos[100])
    assert int(conf_t[100]) == 3          # label 2 -> class id 3 (bg=0)
    np.testing.assert_allclose(np.asarray(loc_t[100]), 0.0, atol=1e-4)


def test_ssd_trains_to_above_chance_map():
    model = SSDLite(num_classes=NCLS, image_size=IMG)
    priors, prior_var = model.priors()

    def loss_fn(module, variables, batch, rng, training):
        img, boxes, labels, nb = batch
        (cls, loc), mut = module.apply(variables, img, training=training,
                                       rngs=rng, mutable=True)
        loss = ssd_loss(cls, loc, priors, boxes, labels, nb)
        return (loss, {}), mut.get("state", {})

    trainer = Trainer(model, Adam(3e-3), loss_fn)
    batches = _batches(bs=8)
    ts = trainer.init_state(jnp.zeros((8, IMG, IMG, 3)))
    first = last = None
    for epoch in range(6):
        for b in batches:
            ts, fetches = trainer.train_step(ts, b)
            if first is None:
                first = float(fetches["loss"])
    last = float(fetches["loss"])
    assert last < first * 0.7, (first, last)

    # evaluate mAP on the training set (capability check, not generalization)
    mAP = DetectionMAP(overlap_threshold=0.4)
    eval_fn = jax.jit(lambda v, x: model.apply(v, x, training=False))
    for img, boxes, labels, nb in batches:
        cls, loc = eval_fn(ts.variables, jnp.asarray(img))
        dets, counts = ssd_detect(cls, loc, priors, prior_var,
                                  score_threshold=0.25)
        for i in range(img.shape[0]):
            d = np.asarray(dets[i][:int(counts[i])])
            g = np.concatenate([np.asarray(labels[i][:int(nb[i])])[:, None],
                                np.asarray(boxes[i][:int(nb[i])])], axis=1)
            mAP.update(d, g)
    score = mAP.eval()
    assert score > 0.15, f"mAP {score} not above chance"
