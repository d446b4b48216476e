"""A child's log is read while the child writes it: a line counts once it
is whole. (A chip run of PR 29 failed in ``Proc.ask`` on the first 2,955
characters of a 3 KB ``PERFBENCH`` answer.)"""

import json
import threading

from perfbench.harness.procs import Proc


class Child:
    """Stands for the process: takes commands, never exits."""

    class stdin:
        write = staticmethod(lambda text: None)
        flush = staticmethod(lambda: None)

    returncode = None

    def poll(self):
        return None


def test_a_line_half_written_is_not_read_until_it_is_whole(tmp_path):
    log = tmp_path / "server.log"
    answer = "PERFBENCH " + json.dumps({"cmd": "stats",
                                        "compile_times": [0.5] * 400})
    log.write_text('PERFBENCH {"cmd": "trace_stop"}\nSERVING x\n'
                   + answer[:2955])
    proc = Proc("server", Child(), str(log))
    assert proc.lines("PERFBENCH ") == ['PERFBENCH {"cmd": "trace_stop"}']
    assert proc.lines("SERVING ") == ["SERVING x"]

    def finish():
        with open(log, "a") as f:
            f.write(answer[2955:] + "\n")

    timer = threading.Timer(0.3, finish)
    timer.start()
    try:
        got = proc.ask("stats", timeout=10)
    finally:
        timer.join(timeout=10)
    assert not timer.is_alive()
    assert got["cmd"] == "stats" and len(got["compile_times"]) == 400
    assert proc.wait_line("PERFBENCH ", 1, nth=2) == answer
