set -x
mkdir -p chiprun_out/pr35
python3 ci/chip_calls/pr35/step_forms.py --forms parent,barrier,roll,stacked,parent --fill 4,32 > chiprun_out/pr35/step_forms.log 2>&1; echo rc=$?
grep -a "step ms" chiprun_out/pr35/step_forms.log
tail -c 3000 chiprun_out/pr35/step_forms.log
