# PR 38, call 1 (four chips): the cell's train step in one process, the parent's form against the
# change's of that hour (the residual over tp only), both traced.
mkdir -p chiprun_out/pr38
python ci/chip_calls/pr38/step_forms.py --forms parent,change --steps 15 --trace parent,change --out chiprun_out/pr38/call1 2>&1 | grep -v "^W0\|^I0\|^E0" | tee chiprun_out/pr38/call1.log | cut -c1-3000
