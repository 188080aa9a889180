"""A seeded corpus of short videos: the generator every corpus traffic file
(``"generator": "video_corpus"``) is read by.

After ``bench.py``'s generator (two scenes a video, global motion a codec
cannot collapse to a still, texture that survives a resize, one tracked
block), made cheap: a scene is a strip of twice the frame's width, periodic
in x, and frame ``f`` is a window into it that moves ``speed`` pixels a frame
(a camera pan), so a frame costs a copy and not a float pass over 720p.
``distinct`` videos are rendered, each from a generator of its own seeded by
(seed, index); the corpus is filled to ``n_videos`` with links to them under
new names (the pipeline keys a video by its path). The corpus is kept under
``.perfbench_cache/corpus/<key>``, keyed by generator version, seed and
parameters, so a seed seen before costs nothing.

Parameters: width, height, fps, scenes, scene_frames, distinct, n_videos,
warm_videos.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

VERSION = 1
KEYED = ("width", "height", "fps", "scenes", "scene_frames", "distinct")


def _scene_strip(rng: np.random.Generator, w: int, h: int) -> np.ndarray:
    """uint8 [h, 2w, 3], periodic in x with period w."""
    import cv2

    c0 = rng.integers(0, 255, 3).astype(np.float32)
    c1 = rng.integers(0, 255, 3).astype(np.float32)
    tilt = rng.uniform(-0.5, 0.5)  # the gradient leans, so rows differ
    x = np.arange(w, dtype=np.float32)[None, :] + tilt * np.arange(h, dtype=np.float32)[:, None]
    tri = np.abs(2.0 * ((x / w) % 1.0) - 1.0)[..., None]  # seamless at the period
    noise = rng.integers(0, 60, (h // 4, w // 4, 3), dtype=np.uint8)
    noise = cv2.resize(noise, (w, h), interpolation=cv2.INTER_LINEAR).astype(np.float32)
    tile = np.clip(c0 * (1.0 - tri) + c1 * tri + noise - 30.0, 0, 255).astype(np.uint8)
    return np.concatenate([tile, tile], axis=1)


def render_video(path: Path, seed: int, index: int, p: dict) -> None:
    import cv2

    w, h = int(p["width"]), int(p["height"])
    rng = np.random.default_rng([int(seed), index])
    writer = cv2.VideoWriter(str(path), cv2.VideoWriter_fourcc(*"mp4v"), float(p["fps"]), (w, h))
    if not writer.isOpened():
        raise RuntimeError(f"cv2 cannot write {path} (mp4v)")
    try:
        side = max(8, h // 5)
        for scene in range(int(p["scenes"])):
            strip = _scene_strip(rng, w, h)
            speed = int(rng.integers(2, 9))
            block = (255 - strip[0, 0]).astype(np.uint8)
            bx = int(rng.integers(0, w - side))
            bvx = int(rng.integers(3, 11)) * (1 if scene % 2 == 0 else -1)
            top = (h - side) // 2
            for f in range(int(p["scene_frames"])):
                off = (f * speed) % w
                frame = np.ascontiguousarray(strip[:, off : off + w])
                x = (bx + f * bvx) % (w - side)
                frame[top : top + side, x : x + side] = block
                writer.write(frame)
    finally:
        writer.release()


def corpus_key(params: dict, seed: int) -> str:
    blob = json.dumps([VERSION, int(seed), {k: params[k] for k in KEYED}], sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def _link(src: Path, dst: Path) -> None:
    try:
        os.link(src, dst)
    except OSError:
        shutil.copy(src, dst)


def make_corpus(params: dict, seed: int, cache_root: Path) -> tuple[Path, Path, bool]:
    """(directory of ``n_videos`` videos, directory of ``warm_videos`` videos,
    whether the distinct videos came from the cache)."""
    root = cache_root / "corpus" / corpus_key(params, seed)
    distinct = root / "distinct"
    cached = (root / "done").exists()
    if not cached:
        shutil.rmtree(root, ignore_errors=True)
        distinct.mkdir(parents=True)
        n = int(params["distinct"])
        with ThreadPoolExecutor(max_workers=min(4, n)) as pool:
            jobs = [
                pool.submit(render_video, distinct / f"d{i:02d}.mp4", seed, i, params)
                for i in range(n)
            ]
            for job in jobs:
                job.result()
        (root / "done").write_text("ok")
    sources = sorted(distinct.glob("*.mp4"))
    out = []
    for name, count in (("videos", int(params["n_videos"])), ("warm", int(params["warm_videos"]))):
        d = root / f"{name}_{count}"
        if not d.exists():
            tmp = root / f".{name}_{count}.{os.getpid()}"
            tmp.mkdir()
            for i in range(count):
                _link(sources[i % len(sources)], tmp / f"v{i:03d}.mp4")
            tmp.rename(d)
        out.append(d)
    return out[0], out[1], cached
