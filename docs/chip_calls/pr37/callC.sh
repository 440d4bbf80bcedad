#!/bin/bash
# PR 37, call C: the way not taken (grouped.py: the 65 distances in five
# groups as a third grid axis, under the default 16 MB of VMEM) beside the
# kernel the program runs, at the cell's shapes.
cd /root/repo
python docs/chip_calls/pr37/grouped.py 2>&1 | grep -v "persistent compilation cache\|warnings.warn"
