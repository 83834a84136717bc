"""Synthetic multi-view scenes: the stub DINO features a VGGT request carries.

Copied from the program's ``data/pipeline.py:scene_batch`` (random camera
poses and a point cloud projected into per-frame patch embeddings by a
fixed random projection), so the benchmark's inputs cannot change with
the program.  A scene is a pure function of (seed, index).
"""
from __future__ import annotations

import numpy as np


def scene(n_frames: int, n_patches: int, d_model: int, index: int, seed: int) -> np.ndarray:
    """One scene's patch embeddings, float32 [1, n_frames, n_patches, d_model]."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1000 + index]))
    pts = rng.normal(size=(1, 1, n_patches, 3)).astype(np.float32)
    pts = np.repeat(pts, n_frames, axis=1)
    pose = rng.normal(size=(1, n_frames, 9)).astype(np.float32) * 0.3
    cam = pts + pose[:, :, None, :3]
    depth = 2.0 + np.abs(cam[..., 2])
    proj_rng = np.random.default_rng(seed + 123)
    w = proj_rng.normal(size=(7, d_model)).astype(np.float32) / np.sqrt(7)
    feats = np.concatenate(
        [cam, depth[..., None], pose[:, :, None, :3].repeat(n_patches, 2)], axis=-1
    )
    patches = feats @ w
    patches += 0.05 * rng.normal(size=patches.shape).astype(np.float32)
    return patches.astype(np.float32)
