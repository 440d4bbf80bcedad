"""One run of benchmark/run.py that also writes the trace's per-operation
table to chiprun_out/<tag>.ops.json. Usage: run_dump.py <tag> <run.py args...>"""
import json, os, runpy, sys
root = os.getcwd()
sys.path.insert(0, root)
from benchmark.reducers import trace as T
tag = sys.argv[1]
orig = T.reduce_dir
def dump(d):
    r = orig(d)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    json.dump({"ops_all": r["ops_all"], "busy_s": r["busy_s"], "window_s": r["window_s"],
               "idle_gaps": r["idle_gaps"]}, open(os.path.join(root, "chiprun_out", tag + ".ops.json"), "w"))
    return r
T.reduce_dir = dump
sys.argv = [os.path.join(root, "benchmark", "run.py")] + sys.argv[2:]
runpy.run_path(sys.argv[0], run_name="__main__")
