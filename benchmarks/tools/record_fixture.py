"""Record a real trace of one cell on the chip and keep it in the plain
form: a summary of what the planes hold, and the first few fit cycles cut
out as ``chiprun_out/fixture_<cell>.json.gz`` (the source of
``benchmarks/fixtures/``).

    python benchmarks/tools/record_fixture.py <cell> [seconds] [cycles]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run_cell  # noqa: E402
from benchmarks.harness import spec, trace_reduce  # noqa: E402


def main(argv) -> int:
    cell = argv[0]
    seconds = float(argv[1]) if len(argv) > 1 else 4.0
    cycles = int(argv[2]) if len(argv) > 2 else 3
    out_dir = spec.ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)

    def keep(plain):
        summary = []
        for plane in plain["planes"]:
            for line in plane["lines"]:
                names = {}
                for name, _, dur in line["events"]:
                    short = trace_reduce.op_name(name)
                    names[short] = names.get(short, 0) + dur
                top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
                summary.append({"plane": plane["name"], "line": line["name"],
                                "events": len(line["events"]), "top": top})
        (out_dir / f"trace_summary_{cell}.json").write_text(
            json.dumps(summary, indent=1))
        fits = [s for s in trace_reduce.host_spans(plain)
                if s[0] == trace_reduce.FIT_SPAN]
        if len(fits) > cycles:
            lo, hi = fits[0][1] - 1000, fits[cycles][2] + 1000
            small = trace_reduce.cut(plain, lo, hi)
            for plane in small["planes"]:
                for line in plane["lines"]:
                    for ev in line["events"]:
                        ev[1] -= lo
            trace_reduce.dump_json_gz(
                small, out_dir / f"fixture_{cell}.json.gz")
            (out_dir / f"fixture_{cell}.expected.json").write_text(
                json.dumps(trace_reduce.reduce(small), indent=1))

    run_cell.configure_compile_cache()
    return run_cell.run(cell, 20250925, seconds, True, on_trace=keep)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
