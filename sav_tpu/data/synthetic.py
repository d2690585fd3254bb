"""Synthetic / fake data iterators.

Parity with the reference's ``fake_data`` branch (/root/reference/
input_pipeline.py:104-113 — correctly-shaped zero batches used as the
built-in fake backend for driver testing), plus a random-data variant for
train-step smoke tests (loss must decrease on a learnable signal).
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np


def fake_data_iterator(
    *,
    batch_size: int,
    image_size: int = 224,
    num_classes: int = 1000,
    transpose: bool = False,
    dtype=np.float32,
) -> Iterator[dict]:
    """Infinite zero batches with the pipeline's exact output shapes."""
    img_shape = (
        (image_size, image_size, 3, batch_size)
        if transpose
        else (batch_size, image_size, image_size, 3)
    )
    images = np.zeros(img_shape, dtype)
    labels = np.zeros((batch_size,), np.int32)
    while True:
        yield {"images": images, "labels": labels}


def synth_batch(
    *,
    seed: int,
    position: int,
    batch_size: int,
    image_size: int = 32,
    num_classes: int = 10,
    transpose: bool = False,
    dtype=np.float32,
) -> dict:
    """The deterministic synthetic batch at schedule ``position``.

    Counter-based (Philox keyed on ``(seed, position)``): the batch is a
    pure function of its schedule position, independent of iteration
    history — which makes the stream *resumable by construction* (restart
    at any step and the batches match the uninterrupted run bit-for-bit)
    and lets an external verifier (tools/chaos_soak.py) recompute any
    position's batch, fingerprint it with the flight recorder's blake2b
    machinery, and prove a resumed child picked up step-exact. The class
    id is embedded as a brightness offset (the learnable signal the
    train-step tests rely on), so loss curves carry information.

    Positions are 1-indexed completed-step numbers, matching the
    recorder's ring entries and ``--skip-steps`` semantics. ``transpose``
    lays the same images out HWCN (``TrainConfig.transpose_images``).
    """
    key = np.array([seed & 0xFFFFFFFFFFFFFFFF, position], np.uint64)
    rng = np.random.Generator(np.random.Philox(key=key))
    labels = rng.integers(0, num_classes, (batch_size,), dtype=np.int32)
    images = rng.standard_normal(
        (batch_size, image_size, image_size, 3)
    ).astype(np.float32)
    images += (labels[:, None, None, None] / num_classes - 0.5) * 4.0
    if transpose:
        images = np.ascontiguousarray(images.transpose(1, 2, 3, 0))
    return {"images": images.astype(dtype), "labels": labels}


def synth_resumable_iterator(
    *,
    seed: int,
    start_step: int = 0,
    batch_size: int,
    image_size: int = 32,
    num_classes: int = 10,
    transpose: bool = False,
    num_batches: Optional[int] = None,
    dtype=np.float32,
) -> Iterator[dict]:
    """Infinite (or bounded) stream of :func:`synth_batch` batches from
    position ``start_step + 1`` on — the ``train.py --synth-data`` feed:
    a TF-free, preemption-exact data path for elasticity soaks and
    kill-resume tests (docs/elasticity.md)."""
    position = start_step
    produced = 0
    while num_batches is None or produced < num_batches:
        position += 1
        produced += 1
        yield synth_batch(
            seed=seed,
            position=position,
            batch_size=batch_size,
            image_size=image_size,
            num_classes=num_classes,
            transpose=transpose,
            dtype=dtype,
        )


def synthetic_data_iterator(
    *,
    batch_size: int,
    image_size: int = 32,
    num_classes: int = 10,
    transpose: bool = False,
    seed: int = 0,
    num_batches: Optional[int] = None,
    learnable: bool = True,
    dtype=np.float32,
) -> Iterator[dict]:
    """Random images with (optionally) label-correlated signal.

    With ``learnable=True`` the class id is embedded as a constant brightness
    offset, so a model trained on this stream must show decreasing loss —
    the train-step integration test the reference lacked (SURVEY.md §4).
    """
    rng = np.random.default_rng(seed)
    count = 0
    while num_batches is None or count < num_batches:
        images = rng.standard_normal(
            (batch_size, image_size, image_size, 3)
        ).astype(dtype)
        labels = rng.integers(0, num_classes, (batch_size,), dtype=np.int32)
        if learnable:
            images += (labels[:, None, None, None] / num_classes - 0.5) * 4.0
        if transpose:
            images = np.transpose(images, (1, 2, 3, 0))
        yield {"images": images.astype(dtype), "labels": labels}
        count += 1
