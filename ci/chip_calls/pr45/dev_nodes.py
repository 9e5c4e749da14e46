"""What a process that opens the chip holds open, and how long it takes to
give it back. The parent stays off jax. Prints: the device nodes there are,
the /proc/<pid>/fd links of a child that has opened jax's TPU backend (with
`TPU_VISIBLE_CHIPS` as given, one child a value of `--visible`), what a second
opener reads while the first lives, and the seconds from SIGTERM / SIGKILL of
the holder to its pid and its links being gone."""
import argparse
import glob
import os
import signal
import subprocess
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--visible", default="", help="comma list of TPU_VISIBLE_CHIPS values to try, '' = unset; ';' between children")
ap.add_argument("--node-chips", type=int, default=1)
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../../..")))
from ray_tpu.core import chips

HOLD = ("import jax, time, sys; d = jax.devices(); "
        "x = jax.numpy.ones((1024, 1024)).block_until_ready(); "
        "print('READY', [str(i) for i in d], flush=True); time.sleep(1000)")


def sh(cmd):
    print("$", cmd, flush=True)
    print(subprocess.run(cmd, shell=True, capture_output=True, text=True).stdout, flush=True)


def dev_links(pid):
    out = []
    for fd in glob.glob(f"/proc/{pid}/fd/*"):
        try:
            to = os.readlink(fd)
        except OSError:
            continue
        if to.startswith("/dev/") and not to.startswith(("/dev/null", "/dev/pts", "/dev/shm")):
            out.append(to)
    return sorted(out)


def hold(visible):
    env = dict(os.environ)
    if visible:
        ids = [int(i) for i in visible.split(",")]
        env.update(chips.chip_visibility_env(ids, args.node_chips))
    p = subprocess.Popen([sys.executable, "-c", HOLD], env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    t = time.time()
    for line in p.stdout:
        if line.startswith("READY"):
            print(f"holder pid {p.pid} visible={visible!r} ready after {time.time() - t:.1f}s:", line.strip(), flush=True)
            break
    else:
        print("holder failed", flush=True)
    return p


sh("ls -l /dev/vfio /dev/accel* 2>&1; ls /sys/kernel/iommu_groups 2>&1 | head; env | grep -i tpu; id; cat /sys/kernel/mm/transparent_hugepage/enabled")
for n, visible in enumerate(args.visible.split(";")):
    p = hold(visible)
    print("links:", dev_links(p.pid), flush=True)
    if n == 0:
        t = time.time()
        r = subprocess.run([sys.executable, "-c", "import jax; print(jax.devices())"],
                           capture_output=True, text=True)
        print(f"second opener: rc={r.returncode} after {time.time() - t:.1f}s", r.stderr[-1500:], flush=True)
    sig = signal.SIGTERM if n % 2 == 0 else signal.SIGKILL
    t = time.time()
    p.send_signal(sig)
    seen = None
    while p.poll() is None:
        links = dev_links(p.pid)
        if links != seen:
            print(f"  +{time.time() - t:.2f}s links {links}", flush=True)
            seen = links
        time.sleep(0.05)
    print(f"{sig.name}: pid gone after {time.time() - t:.2f}s rc={p.returncode}", flush=True)
    if n:
        continue
    t = time.time()
    r = subprocess.run([sys.executable, "-c", "import jax; print(jax.devices())"],
                       capture_output=True, text=True)
    print(f"opener right after: rc={r.returncode} after {time.time() - t:.1f}s", r.stdout[-300:], r.stderr[-600:], flush=True)
