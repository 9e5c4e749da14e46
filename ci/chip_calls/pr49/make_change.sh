#!/bin/bash
# The tree as git would commit it (`git add -A` first), unpacked under
# _check/change: what the driver's checkout of this PR holds.
set -e
cd "$(dirname "$0")/../../.."
rm -rf _check/change && mkdir -p _check/change
git archive $(git write-tree) | tar -x -C _check/change
