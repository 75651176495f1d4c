"""Share of the traced window in which a chip ran the collectives between
the chips, averaged over the chips; layer: device (the FSDP gathers of the
weights and reductions of the gradients that XLA puts in).

An op counts when its name, or the computation it calls, is one of the
collectives' names as the TPU compiler gives them: ``all-gather``,
``all-reduce``, ``reduce-scatter`` (also as the fusion that calls
``all-reduce-scatter``), ``collective-permute`` (the ring steps a gather
or a reduction is split into), ``all-to-all``, and the async pairs
``async-collective-start``/``-done`` with the fusions that call an
``async_collective_fusion``.  Own time on each chip's ``XLA Ops`` line is
added over the chips and divided by their number and by the window.  An
async collective's ``-done`` op holds its chip only while the transfer is
not yet hidden behind compute, so this counts exposed communication."""
import re

COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|async[-_]collective")


def is_collective(name: str, calls: str = "") -> bool:
    return bool(COLLECTIVE.search(name) or COLLECTIVE.search(calls))


def read(run):
    if run.kind != "train" or run.trace is None or not run.trace.devices:
        return None
    calls = run.trace.op_calls
    t = run.trace.kernel_s(lambda n: is_collective(n, calls.get(n, "")))
    if t is None:
        return None
    return 100.0 * t / run.trace.devices / run.trace.window_s
