#!/bin/bash
# The parent commit unpacked under _check/parent (listed in .gitignore). This
# PR adds nothing to the benchmark, so nothing is laid over it.
set -e
cd "$(dirname "$0")/../../.."
rm -rf _check/parent && mkdir -p _check/parent
git archive e29e91d01a9988313d003c41ad50341420243675 | tar -x -C _check/parent
