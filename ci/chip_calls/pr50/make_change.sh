#!/bin/bash
# What git would commit of this tree, unpacked under _check/change (listed in
# .gitignore): a run from there shows that the committed files are enough.
set -e
cd "$(dirname "$0")/../../.."
git add -A
rm -rf _check/change && mkdir -p _check/change
git archive $(git write-tree) | tar -x -C _check/change
