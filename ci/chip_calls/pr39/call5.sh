#!/bin/bash
# call 5: six seeds at 0.8 x the knee, then six (others) at 0.65 x, the full window
bash ci/chip_calls/pr39/six_seeds.sh 0.8
sed -i 's/2147483777 2147484001 2147485003 2147486011 2147487017 2147488019/2147489021 2147490023 2147491027 2147492029 2147493031 2147494037/' ci/chip_calls/pr39/six_seeds.sh
bash ci/chip_calls/pr39/six_seeds.sh 0.65
