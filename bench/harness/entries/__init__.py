"""The served entries: one module per way of serving a configuration.

A configuration file names its entry (``"entry": "vision"``), and
``harness.cell.run`` loads ``harness/entries/<entry>.py`` by that name,
as it finds ``metrics/<name>.py`` readers and ``configs/<reference>.py``
references.  An entry module provides

  load_mix(path)                   the entry's traffic mix from its file
  Served(cfg, mix, seed, ref_mod)  the served stack, built from the file's
                                   sizes, the mix and the seed, with:
    sched                          the program's ``Scheduler`` (faults are
                                   planted through it)
    warm()                         run every shape the window will use
    instrument(spans)              the harness's spans around its layers
    drive(seconds, seed, step)     offer the mix for the window
                                   (``harness.drive.run_window`` with the
                                   entry's ``Load``); the
                                   ``harness.drive.Window``
    counters()                     {"steps", "slot_steps", "cache"}: the
                                   scheduler's steps, the requests they
                                   carried, and the entry's own cache
                                   counters, read before and after the
                                   window
    collect(window)                (answers due in the window, how many of
                                   them failed), kept on the host
    release()                      let the served model go
    verdict(answers)               the answers against the plain reference
                                   under the file's ``limits``
                                   (``harness.correct.verdict``'s dict,
                                   with ``reference``: what was run)

Everything else about a run (the device check, set-up time, the trace,
peak memory, metric readers, the result line) is ``harness.cell``'s.
"""
