"""Run one cell several times, each run a new process with another seed,
and print what the driver's check looks at: per end-to-end metric the
median of each set and its spread (distance between the quartiles over the
median). This process never touches JAX, so each child gets the chip.

    python3 perfbench/repeat.py --workload <cell> [--sets 2] [--runs 6]
        [--seed0 100] [--seconds <s>] [--out chiprun_out/<cell>.runs.jsonl]
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    out = args.out or os.path.join(
        os.path.dirname(HERE), "chiprun_out", args.workload + ".runs.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    sets = []
    with open(out, "a") as record:
        for s in range(args.sets):
            lines = []
            for r in range(args.runs):
                cmd = [sys.executable, os.path.join(HERE, "run.py"),
                       "--workload", args.workload, "--trace", "0",
                       "--seed", str(args.seed0 + s * args.runs + r)]
                if args.seconds is not None:
                    cmd += ["--seconds", args.seconds]
                done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
                if done.returncode != 0:
                    raise SystemExit("run failed (rc %d): %s"
                                     % (done.returncode, " ".join(cmd)))
                said = done.stdout.strip().splitlines()
                line = json.loads(said[-1])
                # the earlier lines too: where set-up went, how close the
                # reference was
                record.write(json.dumps({"set": s, "cmd": cmd[2:], **line,
                                         "log": said[:-1]}) + "\n")
                record.flush()
                print("set %d run %d: correct %s %s" % (
                    s, r, line["correct"],
                    {k: v["value"] for k, v in line["metrics"].items()}),
                    flush=True)
                lines.append(line)
            sets.append(lines)
    for name in sets[0][0]["metrics"]:
        for s, lines in enumerate(sets):
            values = [l["metrics"][name]["value"] for l in lines]
            print("%s set %d: median %.6g spread %.4f%% (n=%d; first run "
                  "%.6g)" % (name, s, statistics.median(values),
                             100 * spread(values) if len(values) > 1 else 0.0,
                             len(values), values[0]), flush=True)
    print("all correct: %s" % all(l["correct"] for ls in sets for l in ls))


if __name__ == "__main__":
    main()
