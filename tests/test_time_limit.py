"""Every test runs under a time limit of its own (tests/conftest.py,
``_time_limit``): past it the test FAILS, alone, with the stack of every
thread, and the run goes on. Held here with pytest's own `pytester`: each
case runs a throw-away test file under the conftest's very section, its
default cut to one second."""

import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def limited(pytester):
    """A directory whose conftest is the time limit's section of this
    suite's, with a default of 1 s, and the suite's markers."""
    with open(os.path.join(HERE, "conftest.py")) as f:
        text = f.read()
    banner = "# " + "-" * 75 + "\n"
    section = next(part for part in text.split(banner)
                   if part.lstrip().startswith("import faulthandler"))
    assert "TIME_LIMIT_S = " in section
    pytester.makeconftest("import pytest\n" + re.sub(
        r"TIME_LIMIT_S = \d+", "TIME_LIMIT_S = 1", section))
    with open(os.path.join(HERE, os.pardir, "pytest.ini")) as f:
        pytester.makeini(f.read().replace("testpaths = tests", ""))
    return pytester


def test_a_test_past_its_limit_fails_alone_and_names_the_limit(limited):
    limited.makepyfile("""
        import time

        def test_sleeps():
            time.sleep(60)

        def test_after():
            pass
    """)
    result = limited.runpytest("-p", "no:cacheprovider")
    result.assert_outcomes(failed=1, passed=1)          # not an error
    assert result.duration < 30
    result.stdout.fnmatch_lines([
        "*time limit: test_*::test_sleeps ran past its 1 s*",
        "*in test_sleeps*"])


def test_the_failure_shows_what_every_thread_waited_for(limited):
    limited.makepyfile("""
        import threading

        def test_waits_for_a_thread_that_never_answers():
            never = threading.Event()

            def worker_that_hangs():
                never.wait()

            threading.Thread(target=worker_that_hangs, daemon=True).start()
            never.wait()
    """)
    result = limited.runpytest("-p", "no:cacheprovider")
    result.assert_outcomes(failed=1)
    text = result.stdout.str()
    assert text.count("Thread 0x") + text.count("Current thread 0x") >= 2
    assert "in worker_that_hangs" in text
    assert "in test_waits_for_a_thread_that_never_answers" in text


def test_the_marker_raises_the_limit_for_one_test(limited):
    limited.makepyfile("""
        import time
        import pytest

        @pytest.mark.time_limit(20)
        def test_honestly_long():
            time.sleep(1.5)

        def test_the_default_still_holds_for_the_next():
            time.sleep(1.5)
    """)
    result = limited.runpytest("-p", "no:cacheprovider", "-W", "error")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines(
        ["*test_the_default_still_holds_for_the_next ran past its 1 s*"])


def test_a_test_under_its_limit_is_untouched(limited):
    limited.makepyfile("""
        import signal
        import time

        def test_quick():
            left, _ = signal.getitimer(signal.ITIMER_REAL)
            assert 0 < left <= 1                # armed, for this test
            time.sleep(0.2)

        def test_quick_again():
            left, _ = signal.getitimer(signal.ITIMER_REAL)
            assert 0.9 < left <= 1              # a limit of its own, whole
    """)
    limited.runpytest("-p", "no:cacheprovider").assert_outcomes(passed=2)


def test_the_limit_is_disarmed_after_each_test(limited):
    """A module's fixture that takes longer than the limit to tear down
    (and one that takes longer to set up the NEXT module) is charged to no
    test: between two tests no timer runs and the handler is the
    process's own (a process of its own: in this one the enclosing test's
    timer runs on)."""
    limited.makepyfile(
        test_one="""
        import signal
        import time
        import pytest

        @pytest.fixture(scope="module")
        def slow_to_go():
            yield
            assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
            assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL
            time.sleep(1.5)

        def test_uses_it(slow_to_go):
            pass
        """,
        test_two="""
        import time
        import pytest

        @pytest.fixture(scope="module", autouse=True)
        def slow_to_come():
            time.sleep(0.8)

        def test_pays_for_its_own_second_only():
            time.sleep(0.5)
        """)
    limited.runpytest_subprocess("-p", "no:cacheprovider").assert_outcomes(
        passed=2)


def test_the_limit_fires_in_a_worker_of_the_driver_s_command(limited):
    """`-p xdist -n 1 --dist loadfile`, as the driver runs the suite: the
    worker runs its tests in its main thread, so the signal reaches them."""
    limited.makepyfile("""
        import time

        def test_sleeps_in_a_worker():
            time.sleep(60)

        def test_after():
            pass
    """)
    result = limited.runpytest_subprocess(
        "-p", "no:cacheprovider", "-p", "xdist", "-n", "1", "--dist",
        "loadfile")
    result.assert_outcomes(failed=1, passed=1)
    result.stdout.fnmatch_lines(["*test_sleeps_in_a_worker ran past its 1 s*"])
