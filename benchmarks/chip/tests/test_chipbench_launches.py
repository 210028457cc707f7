"""``decode_launches_per_req.pgen``: the program's count of the codec's
decode launches per product request, in a tiny traced PGEN run on the CPU,
against the chunks those requests decoded; and nothing over a program that
does not count its launches."""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from benchmarks.chip import load, run  # noqa: E402
from benchmarks.chip.context import Context  # noqa: E402
from benchmarks.chip.spec import find_cell, load_reader  # noqa: E402
# the guard of the compile cache
from test_chipbench_cells import no_compile_cache  # noqa: E402,F401

METRIC = "decode_launches_per_req.pgen"
READER = Path(__file__).resolve().parents[1] / "metrics" / f"{METRIC}.py"

#: chunks handed to the codec per request, from the ``codec.decode`` spans
CHUNKS_READER = '''
def read(ctx):
    reads = ctx.done("read")
    chunks = sum(s.attrs.get("chunks", 0) for s in ctx.spans
                 if s.name == "codec.decode")
    return chunks / len(reads) if reads and chunks else None
'''


def test_traced_pgen_counts_fewer_launches_than_chunks(tmp_path):
    base = tiny.make_tree(tmp_path)
    chip = base / "benchmarks" / "chip"
    # no cache: every request decodes every chunk it touches (3 to 6)
    cfg = dict(tiny.STEP_CONFIG, cache_bytes=0)
    (chip / "configs" / "tiny-step.json").write_text(json.dumps(cfg))
    (chip / "metrics" / "chunks_per_req.pgen.py").write_text(CHUNKS_READER)
    bench = json.loads((base / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "chunks_per_req.pgen", "unit": "chunks", "better": "lower",
        "source": "program_span", "layer": "plans and codec",
        "moves": "read_p95_ms", "workloads": ["pgen"]})
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run.run_cell(find_cell("pgen", base), seed=2 ** 31 + 21,
                     seconds=0.6, trace=True)
    assert r["correct"], r["checks"]
    launches = r["metrics"][METRIC]["value"]
    chunks = r["metrics"]["chunks_per_req.pgen"]["value"]
    assert chunks >= 3
    assert 0 < launches < chunks


def test_no_counter_no_launches_metric():
    read = load_reader(READER)
    work = [load.Work("read", 0.0, 0.0, 1.0, nbytes=8)] * 4
    ctx = Context(trace=None, spans=[], counters={}, codec={}, work=work,
                  window_s=1.0, peaks={})
    assert read(ctx) is None
    ctx.counters = {"codec.decode_launches": 10}
    assert read(ctx) == 2.5
    ctx.work = []
    assert read(ctx) is None
