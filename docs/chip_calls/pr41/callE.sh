#!/bin/bash
# Call E: the final tree as git would commit it (.scratch/commit, made by
# prepare.sh), one plain run of the Trinity cell of one cycle on a seed of its
# own: the committed files are enough, and the check passes under the limits
# that call D's readings set.
cd "$(dirname "$0")/../../.."
bash docs/chip_calls/pr41/call.sh pr41E 500 \
  change:trinity_large_gen.serve_p12288_n128_c16:4100001613:0:10
