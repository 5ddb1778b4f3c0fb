"""Child processes and what is read from them from outside: output files,
/metrics expositions, /proc CPU. Copied from chip_smoke.py (Child,
free_port, scrape) and bench.py (_proc_cpu_s), which ran on the chip in
PR 21; the originals stay where they are (PERF.md, Open questions)."""

import os
import socket
import subprocess
import time
import urllib.request


class Child:
    """One child process with its output in files; stopped and waited for
    by close(), whatever happened."""

    def __init__(self, name, argv, env, workdir, cwd):
        self.name = name
        self.out_path = os.path.join(workdir, f"{name}.out")
        self.err_path = os.path.join(workdir, f"{name}.err")
        self._out = open(self.out_path, "wb")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(argv, cwd=cwd, env=env,
                                     stdout=self._out, stderr=self._err)

    @property
    def pid(self):
        return self.proc.pid

    def _read(self, path):
        with open(path, "rb") as f:
            return f.read().decode(errors="replace")

    def stdout(self):
        return self._read(self.out_path)

    def stderr(self):
        return self._read(self.err_path)

    def alive(self):
        return self.proc.poll() is None

    def wait_line(self, prefix, timeout):
        """The first stdout line starting with `prefix`; fails when the
        process dies or the deadline passes first."""
        deadline = time.monotonic() + timeout
        while True:
            for line in self.stdout().splitlines():
                if line.startswith(prefix):
                    return line
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited with {self.proc.returncode} "
                    f"before printing {prefix!r}; stderr tail:\n"
                    f"{self.stderr()[-3000:]}")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{self.name} did not print {prefix!r} within "
                    f"{timeout}s; stderr tail:\n{self.stderr()[-3000:]}")
            time.sleep(0.05)

    def close(self, timeout=60):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._out.close()
        self._err.close()
        return self.proc.returncode


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrape(url):
    """{metric line name-with-labels: value} of a /metrics exposition."""
    with urllib.request.urlopen(url, timeout=10) as resp:
        return parse_metrics(resp.read().decode())


def parse_metrics(text):
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def proc_cpu_s(pid):
    """User + system CPU seconds of a live process (/proc/<pid>/stat)."""
    with open(f"/proc/{pid}/stat") as f:
        parts = f.read().rsplit(")", 1)[1].split()
    return (int(parts[11]) + int(parts[12])) / os.sysconf("SC_CLK_TCK")
