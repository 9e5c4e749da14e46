#!/bin/bash
# The parent commit unpacked under _check/parent (listed in .gitignore), with
# this PR's benchmark files laid over it as the driver lays them: what a new
# cell finds on a program that lacks what this PR adds to the program.
set -e
cd "$(dirname "$0")/../../.."
rm -rf _check/parent && mkdir -p _check/parent
git archive 23edaa2bc8cebfbe3fa2e33015875aedbf75ef15 | tar -x -C _check/parent
cp BENCHMARK.json _check/parent/
cp -r perfbench/. _check/parent/perfbench/
cp -r tests/perfbench/. _check/parent/tests/perfbench/
find _check/parent -name __pycache__ -prune -exec rm -rf {} +
