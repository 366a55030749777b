"""Record the small chip trace the trace-reduction test reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Runs the tiny four-lane fleet cell of the test fixtures once with
``--trace 1`` on the chip and keeps its profiler trace.
"""
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import conftest  # noqa: E402
import run  # noqa: E402


def main(out: str) -> int:
    with tempfile.TemporaryDirectory(dir=os.path.dirname(HERE)) as root:
        for rel, obj in conftest.tiny_files().items():
            path = os.path.join(root, rel)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(obj, f)
        for d in ("layers", "limits"):
            import shutil
            shutil.copytree(os.path.join(conftest.BENCH, d),
                            os.path.join(root, "bench", d))
        res = run.run_cell("tiny.fleet", 5, 1.0, True, root=root,
                           trace_out=os.path.abspath(out))
    print(json.dumps(res))
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
