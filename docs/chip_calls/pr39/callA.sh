#!/bin/bash
# Call A: the MiMo cell, the final tree as git would commit it
# (.scratch/commit) against the parent under this change's benchmark files
# (.scratch/parent), one machine; both made by docs/chip_calls/pr34/prepare.sh
# 920cad1f. Each side's first run is traced (a checkout's first run: the
# checkpoint drawn and written, a cold compile; on the parent it is also the
# old cell traced under the new benchmark files, where the new metric has
# nothing to read); then untraced pairs on a shared seed, parent, change,
# change, parent, and the change on one more seed. Runs that would start
# after 1,500 s are skipped.
cd "$(dirname "$0")/../../.."
mimo=mimo_v2_flash_gen.serve_p9216_n128_c32
bash docs/chip_calls/pr38/call.sh pr39A 1500 \
  change:$mimo:3900000011:1:45 \
  parent:$mimo:3900000011:1:45 \
  parent:$mimo:3900000023:0:45 \
  change:$mimo:3900000023:0:45 \
  change:$mimo:4100000037:0:45 \
  parent:$mimo:4100000037:0:45 \
  change:$mimo:3900000041:0:45
