"""A benchmark root that holds, besides every file of ``bench/``, a
token-serving configuration, its reference and its mixes from
``tests/fixtures/`` as a later configuration would bring them: new files
and new entries of ``BENCHMARK.json``, nothing else changed."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = BENCH / "tests" / "fixtures"
CONFIG = "moe_decoder_tiny"
MIXES = ("tokens.tiny", "tokens.tiny_closed")
CELLS = tuple(f"{CONFIG}.{m}" for m in MIXES)


def bench_with_fixture(root: Path) -> Path:
    """``root/bench`` (returned) and ``root/BENCHMARK.json``."""
    bench = root / "bench"
    bench.mkdir(parents=True)
    for d in ("harness", "metrics"):
        (bench / d).symlink_to(BENCH / d)
    for d, extra in (("configs", (f"{CONFIG}.json", "moe_decoder_ref.py")),
                     ("traffic", tuple(f"{m}.json" for m in MIXES))):
        (bench / d).mkdir()
        for f in (BENCH / d).iterdir():
            (bench / d / f.name).symlink_to(f)
        for name in extra:
            (bench / d / name).symlink_to(FIXTURES / name)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": CONFIG, "source": "tests only",
        "file": f"bench/configs/{CONFIG}.json", "reduced": [],
        "why": "a routed-expert decoder served through the tokens entry"})
    for mix, cell in zip(MIXES, CELLS):
        spec["workloads"].append({"name": cell, "config": CONFIG,
                                  "traffic": mix, "chips": 1,
                                  "why": "token prompts through the LM path"})
    for m in spec["end_to_end"]:
        if m["name"] in ("latency_p50_ms", "latency_p95_ms"):
            m["workloads"] = m["workloads"] + list(CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))
    return bench
