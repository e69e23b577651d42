#!/usr/bin/env python
"""Distributed launcher (ref tools/launch.py + dmlc-tracker).

TPU-native: multi-host SPMD uses jax.distributed — ONE process per host over
DCN, and that process drives every chip of its host. This launcher starts N
worker processes with the coordinator env (COORD_ADDR/NUM_PROC/PROC_ID), the
analog of DMLC_ROLE/DMLC_PS_ROOT_URI for the parameter-server design.

Local mode is CPU-only: N workers on one host would each claim every chip
of that host (a chip belongs to one process), so local workers are started
with JAX_PLATFORMS=cpu. It exists to exercise the multi-process control
flow (tests/test_dist.py); on a TPU host, run one process and give it a
mesh over the host's chips. Remote hosts: run the same command per host with
PROC_ID set (ssh orchestration mirrors dmlc-tracker's ssh mode; exercised
only manually — CI images ship no sshd). The reference's mpi/yarn/sge
launchers are a documented cut: TPU pods are provisioned by the platform
(GKE/queued resources), which owns the role dmlc-tracker's cluster
schedulers played — jax.distributed only needs the coordinator address
this launcher already provides.
"""
import argparse
import os
import subprocess
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--coord-addr", default="127.0.0.1:12321")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    procs = []
    if args.launcher == "local":
        for rank in range(args.num_workers):
            env = dict(os.environ)
            env.update({
                "MXTPU_COORD_ADDR": args.coord_addr,
                "MXTPU_NUM_PROC": str(args.num_workers),
                "MXTPU_PROC_ID": str(rank),
                # N processes on one host cannot share its chips
                "JAX_PLATFORMS": "cpu",
                # DMLC-compat aliases so reference-era scripts keep working
                "DMLC_NUM_WORKER": str(args.num_workers),
                "DMLC_RANK": str(rank),
            })
            procs.append(subprocess.Popen(args.command, env=env))
        code = 0
        for p in procs:
            code |= p.wait()
        sys.exit(code)
    else:
        # dmlc-tracker ssh mode: one worker per rank, hosts assigned
        # round-robin from the hostfile; env rides the remote command line
        # (ssh joins argv into one remote shell string). Exit codes
        # propagate like the local mode. Tested via a PATH-shimmed fake
        # ssh (tests/test_launcher_ssh.py); real-cluster use only needs
        # sshd + shared filesystem, as upstream.
        hosts = [h.strip() for h in open(args.hostfile) if h.strip()]
        if not hosts:
            sys.exit("empty hostfile")
        for rank in range(args.num_workers):
            host = hosts[rank % len(hosts)]
            cmd = ["ssh", host,
                   "MXTPU_COORD_ADDR=%s" % args.coord_addr,
                   "MXTPU_NUM_PROC=%d" % args.num_workers,
                   "MXTPU_PROC_ID=%d" % rank,
                   "DMLC_NUM_WORKER=%d" % args.num_workers,
                   "DMLC_RANK=%d" % rank] + args.command
            procs.append(subprocess.Popen(cmd))
        code = 0
        for p in procs:
            code |= p.wait()
        sys.exit(code)


if __name__ == "__main__":
    main()
