"""Share of the window's admissions in which the host is NOT waiting for
the device: puts and the prefill's dispatch (``launch_s``), the insert's
dispatch (``insert_s``) and padding, arming and spans (``host_s``), over
the admissions' whole time; the rest is ``read_s``, the device's prefill
as the host sees it."""

from benchmark.harness import admissions


def read(out):
    found = admissions.window_admissions(out)
    if found is None or admissions.seconds(found) <= 0:
        return None
    return (100.0 * admissions.total(found, "host_s", "launch_s", "insert_s")
            / admissions.seconds(found))
