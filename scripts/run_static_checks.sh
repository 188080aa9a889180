#!/usr/bin/env bash
# Static-analysis gate: AST lint over the package + the analysis test suite.
# CI and pre-merge hooks call this; it exits nonzero on any finding or test
# failure. See docs/STATIC_ANALYSIS.md for the rule catalogue.
set -euo pipefail

cd "$(dirname "$0")/.."

echo "== curate-lint: AST rules + shardcheck + concurrency + schema over cosmos_curate_tpu/ =="
# `cosmos-curate-tpu lint` when the console script is installed; module
# invocation otherwise (dev checkouts without `pip install -e .`).
# --shard-check is device-free (jax.eval_shape over an AbstractMesh), so
# it runs on the CPU-only CI image with zero device allocation.
# --concurrency adds the whole-repo lock-order graph / blocking-under-lock
# / guarded-by pass (analysis/concurrency_check.py) — the repo must stay
# concurrency-clean.
# --schema diffs the wire/durable contract surfaces against the
# analysis/schemas/ goldens (analysis/schema_check.py) — drift without a
# version bump, or a breaking durable bump without a migration shim, fails.
if command -v cosmos-curate-tpu >/dev/null 2>&1; then
  JAX_PLATFORMS=cpu cosmos-curate-tpu lint --shard-check --concurrency --schema cosmos_curate_tpu
else
  JAX_PLATFORMS=cpu python -m cosmos_curate_tpu.cli.main lint --shard-check --concurrency --schema cosmos_curate_tpu
fi

echo "== analysis test suite =="
JAX_PLATFORMS=cpu python -m pytest tests/analysis -q

echo "== tracing smoke: 2-stage traced run -> one connected trace + run report =="
# The programmatic equivalent of a `--tracing` run: two trivial stages
# through the pipelined runner (thread-pool hop included), then the flight
# recorder must see exactly ONE trace id and write a well-formed
# report/run_report.json that the report CLI can render.
JAX_PLATFORMS=cpu python - <<'PY'
import json, tempfile
from pathlib import Path

from cosmos_curate_tpu.core.pipeline import run_pipeline
from cosmos_curate_tpu.core.pipelined_runner import PipelinedRunner
from cosmos_curate_tpu.core.stage import Stage
from cosmos_curate_tpu.core.tasks import PipelineTask
from cosmos_curate_tpu.observability import tracing
from cosmos_curate_tpu.observability.flight_recorder import render_report, write_run_report


class Tok(PipelineTask):
    def __init__(self, v):
        self.v = v


class Inc(Stage):
    thread_safe = True

    def process_data(self, tasks):
        return [Tok(t.v + 1) for t in tasks]


class Dbl(Stage):
    thread_safe = True

    def process_data(self, tasks):
        return [Tok(t.v * 2) for t in tasks]


out = tempfile.mkdtemp(prefix="trace_smoke_")
tracing.enable_tracing(f"{out}/profile/traces/driver.ndjson")
runner = PipelinedRunner()
res = run_pipeline([Tok(i) for i in range(8)], [Inc(), Dbl()], runner=runner)
tracing.disable_tracing()
assert sorted(t.v for t in res) == [(i + 1) * 2 for i in range(8)]

report = write_run_report(out, runner=runner)
assert report["connected"] and len(report["trace_ids"]) == 1, (
    f"trace fragments: {report['trace_ids']}"
)
data = json.loads(Path(out, "report", "run_report.json").read_text())
assert data["span_count"] >= 4 and data["critical_path"], data
assert data["critical_path"][0]["name"] == "pipeline.run"
assert "stage_times" in data and "dead_lettered" in data
render_report(data)  # must not raise
print(f"tracing smoke ok: {data['span_count']} spans, one connected trace")
PY

echo "== paged-attention parity smoke: kernel vs gather, same prompts =="
# The paged programs (attention reads the KV pool through the block table)
# and the legacy gather-view programs must caption IDENTICALLY on the same
# prompts — greedy byte parity is the contract that lets auto-mode flip
# between them per platform.
JAX_PLATFORMS=cpu python - <<'PY'
from cosmos_curate_tpu.models.vlm import (
    CaptionEngine, CaptionRequest, SamplingConfig, VLM_TINY_TEST,
)

def drive(mode, params=None):
    eng = CaptionEngine(
        VLM_TINY_TEST, max_batch=2, kv_lanes=((64, 1), (128, 1)),
        prefill_chunk=16, paged_attention=mode,
    )
    eng.setup()
    if params is not None:
        eng.params = params
    tok = eng.tokenizer
    for i, text in enumerate(("a quiet street at dusk", "close-up of rain " * 6)):
        eng.add_request(CaptionRequest(
            request_id=f"r{i}", prompt_ids=tok.encode(text),
            sampling=SamplingConfig(max_new_tokens=12),
        ))
    out = {r.request_id: r.text for r in eng.run_until_complete()}
    return out, eng

kernel_out, kernel_eng = drive("auto")
gather_out, gather_eng = drive("gather", kernel_eng.params)
assert kernel_out == gather_out, (kernel_out, gather_out)
assert kernel_eng.paged_kernel_steps > 0 and gather_eng.paged_kernel_steps == 0
print(f"paged parity smoke ok: {len(kernel_out)} prompts bit-equal across paths")
PY

echo "static checks passed"
