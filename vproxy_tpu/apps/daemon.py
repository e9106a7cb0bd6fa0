"""Daemon — supervisor for crash-restart and zero-downtime reload.

Parity: reference `vproxyx/Daemon.java:15-70`: forks a child running
the real app, watches its health, restarts it if it dies; SIGUSR2
launches a NEW child first (binds overlap via SO_REUSEPORT /
noStartupBindCheck), then stops the old one once the new one is up —
zero-downtime config reload.

One process per chip: this supervisor never touches JAX (main.py
dispatches here before any device bring-up). Each child claims the
device at boot and prints what it got (main.boot_device_line); the
supervisor relays the child's stdout and remembers the platform. Once a
child has served from an accelerator, a later child that comes up on
the CPU is a FAILED child — a reload must not end on the CPU
unnoticed. Measured on the v5e host (PR 21): a second process that
touches the chip while the old child holds it does not fall back, it
dies in ~3 s with "Unable to initialize backend 'tpu': ABORTED: The
TPU is already in use by process with pid N" — so on a chip host the
overlapped reload fails closed (new child exits, old one kept).
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import List, Optional

CHECK_INTERVAL_S = 1.0
RESTART_DELAY_S = 1.0
RELOAD_GRACE_S = 5.0
BOOT_TIMEOUT_S = 120.0  # a new child must report its device by then


class Daemon:
    def __init__(self, child_args: List[str]):
        self.child_args = child_args
        self.child: Optional[subprocess.Popen] = None
        self.stopping = False
        self.reload_requested = False
        self._lock = threading.Lock()
        self.accelerator: Optional[str] = None  # first non-cpu platform

    def _spawn(self) -> subprocess.Popen:
        from ..main import DEVICE_LINE
        cmd = [sys.executable, "-m", "vproxy_tpu",
               "noStdIOController"] + self.child_args
        # unbuffered: the child's stdout is now a pipe, and its lines
        # must still reach the operator as they are printed
        p = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                             env={**os.environ, "PYTHONUNBUFFERED": "1"})
        p.platform = None  # set by the relay thread from the boot line

        def relay() -> None:
            for line in p.stdout:
                if line.startswith(DEVICE_LINE):
                    p.platform = line[len(DEVICE_LINE):].split()[0]
                    if self.accelerator is None and p.platform != "cpu":
                        self.accelerator = p.platform
                sys.stdout.write(line)
                sys.stdout.flush()

        threading.Thread(target=relay, daemon=True,
                         name=f"daemon-relay-{p.pid}").start()
        return p

    def _lost_accelerator(self, p) -> bool:
        """True when child *p* reported the CPU platform after an
        earlier child of this supervisor served from an accelerator."""
        return self.accelerator is not None and p.platform == "cpu"

    def request_reload(self, *_a) -> None:
        self.reload_requested = True

    def request_stop(self, *_a) -> None:
        self.stopping = True

    def _do_reload(self) -> None:
        """new child first, old child second (reuseport overlap). The
        new child counts as up once it outlived the grace window AND
        reported its device (it claims the device before it binds
        anything, so a child that cannot have the chip dies first)."""
        old = self.child
        new = self._spawn()
        t0 = time.time()
        while time.time() - t0 < RELOAD_GRACE_S or new.platform is None:
            if new.poll() is not None:  # new child died: keep the old
                print("daemon: reload failed, new child exited "
                      f"{new.returncode}; keeping old", file=sys.stderr)
                return
            if time.time() - t0 > BOOT_TIMEOUT_S:
                new.kill()
                print("daemon: reload failed, new child reported no "
                      f"device within {BOOT_TIMEOUT_S:.0f}s; keeping old",
                      file=sys.stderr)
                return
            time.sleep(0.2)
        if self._lost_accelerator(new):
            new.kill()
            print("daemon: reload failed, new child came up on the cpu "
                  f"on a {self.accelerator} host; keeping old",
                  file=sys.stderr)
            return
        self.child = new
        if old is not None and old.poll() is None:
            old.send_signal(signal.SIGTERM)
            try:
                old.wait(timeout=10)
            except subprocess.TimeoutExpired:
                old.kill()
        print("daemon: reloaded", file=sys.stderr)

    def run(self) -> int:
        signal.signal(signal.SIGTERM, self.request_stop)
        signal.signal(signal.SIGINT, self.request_stop)
        if hasattr(signal, "SIGUSR2"):
            signal.signal(signal.SIGUSR2, self.request_reload)
        self.child = self._spawn()
        print(f"daemon: child pid {self.child.pid}", file=sys.stderr)
        while not self.stopping:
            time.sleep(CHECK_INTERVAL_S)
            if self.reload_requested:
                self.reload_requested = False
                self._do_reload()
                continue
            if self._lost_accelerator(self.child):
                print(f"daemon: child came up on the cpu on a "
                      f"{self.accelerator} host; restarting it",
                      file=sys.stderr)
                self.child.kill()
                self.child.wait()
            if self.child.poll() is not None:
                print(f"daemon: child exited {self.child.returncode}, "
                      "restarting", file=sys.stderr)
                time.sleep(RESTART_DELAY_S)
                if not self.stopping:
                    self.child = self._spawn()
        if self.child is not None and self.child.poll() is None:
            self.child.send_signal(signal.SIGTERM)
            try:
                self.child.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.child.kill()
        return 0


def run(argv: List[str]) -> int:
    return Daemon(argv).run()
