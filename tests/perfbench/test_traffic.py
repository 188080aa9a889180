"""Traffic is a pure function of the seed and the parameters in the data file."""

import json

import numpy as np
import pytest

from perfbench import catalog
from perfbench.traffic import caption_requests, video_corpus

CAPTION_MIXES = ["windows-32f", "text-rewrite"]


def _traffic(mix, seed, rehearse=True):
    raw = json.loads((catalog.HERE / "traffic" / f"{mix}.json").read_text())
    params = dict(raw["params"], **(raw["rehearse"] if rehearse else {}))
    return caption_requests.CaptionTraffic(params, seed, vocab=1000, image_size=32)


@pytest.mark.parametrize("mix", CAPTION_MIXES)
def test_caption_requests_repeat_with_the_seed(mix):
    a, b, other = _traffic(mix, 5), _traffic(mix, 5), _traffic(mix, 6)
    for i in (0, 3, 17):
        ra, rb, ro = a.request(i), b.request(i), other.request(i)
        assert ra.prompt_ids == rb.prompt_ids and ra.prefix_ids == rb.prefix_ids
        assert (ra.frames is None) == (rb.frames is None)
        if ra.frames is not None:
            assert np.array_equal(ra.frames, rb.frames) and ra.frames.dtype == np.uint8
        assert ra.prompt_ids != ro.prompt_ids
    # order of asking does not matter: request i has a generator of its own
    assert a.request(9).prompt_ids == _traffic(mix, 5).request(9).prompt_ids


@pytest.mark.parametrize("mix", CAPTION_MIXES)
def test_caption_request_lengths_follow_the_data_file(mix):
    t = _traffic(mix, 1, rehearse=False)
    p = t.params
    lengths = {len(t.request(i).prompt_ids) for i in range(60)} if p["frames"] == 0 else {
        int(np.random.default_rng([1, 1, i]).choice(t.grid)) for i in range(60)
    }
    assert lengths <= set(t.grid) and len(lengths) > 1
    assert t.grid[0] == p["prompt_tokens"]["min"] and t.grid[-1] <= p["prompt_tokens"]["max"]
    r = _traffic(mix, 1).request(0)
    assert len(r.prefix_ids) == _traffic(mix, 1).params["prefix_tokens"]
    assert min(r.prompt_ids + r.prefix_ids) >= 500  # clear of the specials
    fixed = _traffic(mix, 1).request(4, name="warm8", prompt_len=8, max_new_tokens=1)
    assert (fixed.request_id, len(fixed.prompt_ids), fixed.max_new_tokens) == ("warm8", 8, 1)


def test_fresh_frames_for_every_request():
    t = _traffic("windows-32f", 2)
    assert not np.array_equal(t.request(0).frames, t.request(1).frames)
    assert t.request(0).frames.shape == (t.params["frames"], 32, 32, 3)


def test_text_only_check_request_is_seeded_and_exact():
    t = _traffic("text-rewrite", 3)
    r = t.text_only("check-text-20", 20)
    assert len(r.prompt_ids) == 20 and r.prefix_ids == [] and r.frames is None
    assert r.prompt_ids == _traffic("text-rewrite", 3).text_only("x", 20).prompt_ids


def test_video_corpus_repeats_with_the_seed_and_is_cached(tmp_path):
    cv2 = pytest.importorskip("cv2")
    raw = json.loads((catalog.HERE / "traffic" / "fixed-stride-720p.json").read_text())
    params = {**raw["params"], **raw["rehearse"], "width": 64, "height": 48, "scene_frames": 6}
    vids, warm, cached = video_corpus.make_corpus(params, 4, tmp_path / "a")
    assert not cached and len(list(vids.glob("*.mp4"))) == params["n_videos"]
    assert len(list(warm.glob("*.mp4"))) == params["warm_videos"]
    again, _, cached = video_corpus.make_corpus(params, 4, tmp_path / "a")
    assert cached and again == vids
    other, _, _ = video_corpus.make_corpus(params, 4, tmp_path / "b")
    first = sorted(vids.glob("*.mp4"))[0]
    assert first.read_bytes() == (other / first.name).read_bytes()
    assert video_corpus.corpus_key(params, 4) != video_corpus.corpus_key(params, 5)
    cap = cv2.VideoCapture(str(first))
    n = int(cap.get(cv2.CAP_PROP_FRAME_COUNT))
    cap.release()
    assert n == params["scenes"] * params["scene_frames"]
