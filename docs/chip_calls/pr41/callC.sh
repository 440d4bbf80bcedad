#!/bin/bash
# Call C: the final tree as git would commit it (.scratch/commit, made by
# prepare.sh), one plain run of the Trinity cell on a seed of its own: the
# committed files are enough, and one more reading of the rate and of the
# comparison.
cd "$(dirname "$0")/../../.."
bash docs/chip_calls/pr41/call.sh pr41C 600 \
  change:trinity_large_gen.serve_p12288_n128_c16:4100000811:0:45
