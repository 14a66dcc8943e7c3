"""`benchmark/run.py` with a `Telemetry` configured to a directory: what the
program's own tracing costs when it is on.

    python3 benchmark/tools/run_telemetry.py <dir> --workload <cell> --seed <n> --seconds <s> --trace 0

The arguments after <dir> are run.py's.  The run's result line is the same;
beside it the directory holds `bench.spans.jsonl` with every `serve/` span of
the run (pre-roll included), each carrying `iter` and `req`, and the engine's
`request` and `serving_window` records.  Compare the line with an ordinary
run's on the same seed (PERF.md section 6, PR 24)."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv) -> int:
    from benchmark import run
    from dalle_pytorch_tpu.observability import telemetry

    tele = telemetry.configure(dir=argv[0], run_name="bench", heartbeat_s=None,
                               watch_compiles=False)
    try:
        return run.main(argv[1:])
    finally:
        tele.close()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
