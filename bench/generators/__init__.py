"""Traffic generators.  A traffic file (``bench/traffic/<name>.json``)
names its generator (``"generator"``), a module here that makes the
traffic from the file's parameters, the configuration and the seed,
drives the program with it, and checks what the program answered
against ``bench/reference``.

A generator module defines ``build(cfg, traffic, seed)``, which makes the
cell's data on the device and returns an object with:

  warm()            run every shape the window will use (set-up)
  window(seconds)   the measured closed loop
  end_to_end()      {metric: value} of the window
  attempted, failed counts of the window's transactions or queries
  collect()         read back what the check needs, before the state goes
  free()            drop the program's state
  checks()          [common.Check] from the reference, after free()

and ``control()``, a context manager under which the program breaks one
guarantee the configuration states (the control that must fail)."""
