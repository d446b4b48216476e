"""Process plumbing for the benchmark's parent, which never imports JAX:
it starts the roles, waits for their lines, and stops every one of them."""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List


class BenchFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def handshake_fields(line: str) -> Dict[str, str]:
    return {k: v.strip('"') for k, v in
            re.findall(r'(\w+)=("[^"]*"|\S+)', line)}


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as exc:
        return f"<{exc}>"


class Proc:
    def __init__(self, name: str, popen: subprocess.Popen, log: str):
        self.name, self.popen, self.log = name, popen, log
        self.t_spawn = time.time()

    def send(self, text: str) -> None:
        self.popen.stdin.write(text + "\n")
        self.popen.stdin.flush()

    def lines(self, prefix: str) -> List[str]:
        """The log's COMPLETE lines that start with `prefix`: the child may
        be half way through writing its last one (a 3 KB answer is not
        flushed in one piece), and half a JSON object does not parse."""
        with open(self.log, errors="replace") as f:
            return [l[:-1] for l in f
                    if l.endswith("\n") and l.startswith(prefix)]

    def wait_line(self, prefix: str, timeout: float, nth: int = 1) -> str:
        """The nth line of the log that starts with `prefix`."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            got = self.lines(prefix)
            if len(got) >= nth:
                return got[nth - 1]
            if self.popen.poll() is not None:
                raise BenchFailure(
                    f"{self.name}: exited rc={self.popen.returncode} before "
                    f"printing {prefix!r}:\n{tail(self.log)}")
            time.sleep(0.05)
        raise BenchFailure(f"{self.name}: no {prefix!r} within "
                           f"{timeout:.0f}s:\n{tail(self.log)}")

    def ask(self, cmd: str, timeout: float = 120.0) -> dict:
        """Send a serve_shim command and return its PERFBENCH answer."""
        key = cmd.split()[0]
        seen = sum(1 for l in self.lines("PERFBENCH ")
                   if json.loads(l[10:]).get("cmd") == key)
        self.send(cmd)
        deadline = time.time() + timeout
        while time.time() < deadline:
            got = [json.loads(l[10:]) for l in self.lines("PERFBENCH ")]
            got = [g for g in got if g.get("cmd") == key]
            if len(got) > seen:
                if "error" in got[-1]:
                    raise BenchFailure(f"{self.name}: {cmd}: "
                                       f"{got[-1]['error']}")
                return got[-1]
            if self.popen.poll() is not None:
                break
            time.sleep(0.05)
        raise BenchFailure(f"{self.name}: no answer to {cmd!r}:\n"
                           f"{tail(self.log)}")


class Procs:
    """Everything one run started, so that it can stop all of it."""

    def __init__(self, out_dir: str, cwd: str):
        self.out, self.cwd = out_dir, cwd
        self.all: List[Proc] = []

    def spawn(self, name: str, argv: List[str], env: dict) -> Proc:
        log = os.path.join(self.out, name + ".log")
        with open(log, "w") as f:
            popen = subprocess.Popen(
                argv, cwd=self.cwd, env=env, stdout=f,
                stderr=subprocess.STDOUT, stdin=subprocess.PIPE, text=True)
        proc = Proc(name, popen, log)
        self.all.append(proc)
        return proc

    def stop(self, proc: Proc, timeout: float = 60.0) -> None:
        """SIGINT (the program's clean exit), then WAIT: the next owner of
        the chip may not start while this one still holds it."""
        if proc.popen.poll() is None:
            proc.popen.send_signal(signal.SIGINT)
            try:
                proc.popen.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.popen.kill()
                proc.popen.wait(timeout=30)

    def stop_all(self) -> None:
        for p in self.all:
            if p.popen.poll() is None:
                p.popen.kill()
        for p in self.all:
            try:
                p.popen.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass

    def wait_exit(self, proc: Proc, timeout: float) -> int:
        try:
            return proc.popen.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.popen.kill()
            proc.popen.wait(timeout=30)
            raise BenchFailure(f"{proc.name}: no exit within {timeout:.0f}s"
                               f":\n{tail(proc.log)}") from None


def python_argv(module: str, *args: str) -> List[str]:
    return [sys.executable, "-m", module, *args]
