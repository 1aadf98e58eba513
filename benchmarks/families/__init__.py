"""Everything of the benchmark that knows a model. A configuration file names
its family (``"family": "dlrm"``) and ``manifest.Cell.family`` loads
``benchmarks/families/<family>.py``, or the package of that name, by path, as
``manifest.read_metric`` loads a reader: a later PR adds a family as new files
and edits nothing that is here.

With ``system.py`` (the program's entry points that belong to no model) the
families are the only code of the benchmark that imports
``distributed_embeddings_tpu``. A family has two halves. Its adapter half
takes from the program its entry points and nothing that decides a metric or
``correct``; its reference half, its weights and its counts of work import
nothing of the program.

What a family answers is what the runner (``benchmarks/lib/runner.py``) and
the tools (``benchmarks/tools/limits.py``, ``sweep.py``) call, by the
``kind`` of the cell's traffic file. ``config`` and ``traffic`` are the
cell's two files as they are read.

both kinds
    ``build(config, traffic, seed)``: the model built, its weights for the
    seed on the device; an object whose ``state`` the compiled step or the
    serving runtime takes (the runner frees it by ``built.state = None``).
    ``WORK[name](config, work, ctx) -> (FLOP, HBM bytes)`` a step, for the
    reader ``roofline``, and ``FLOPS[name](config) -> FLOP a sample``, for
    the reader ``mfu``, by the name in the metric's file.

    Counters: what the program counts with ``obs.counter_inc`` (a family's
    adapter half may bump one of its own there, in the host wrapper of its
    step or of its runtime) the runner reads through ``system.counters()``
    as the window opens and closes, and every counter's rise is in
    ``ctx["counters"]``; of a serving cell every number of ``rt.stats()`` is
    in ``ctx["stats"]``. A metric file reads either by name:
    ``{"reader": "value", "group": "counters" | "stats", "key": ...}``.
    The configuration file's keys are the family's to read, but for those
    that the harness reads itself: ``family`` (this look-up), ``chips`` (1 or
    4, the ``chips`` of every cell that runs it), ``reduced`` (the keys cut
    from the source's values, the list of the ``configs`` entry) and, where
    that is not empty, ``published`` (the source's own value of each reduced
    key) and ``deployment`` (over how many chips each layer is divided, and
    how). ``check_config_entry`` of
    ``tests/benchmark/test_benchmark_manifest.py`` holds every file to it.

``"kind": "train"``
    ``train_batches(config, traffic, seed)``: the host batches of a seed.
    ``stage(built, batch)``: one batch on the device, the tuple of
    arguments that the step takes after the state.
    ``train_step(built, traffic)``: the compiled step,
    ``(state, *staged) -> (loss, state)``.
    ``samples_per_step(config, traffic)``: what one step adds to
    ``samples_per_s``.
    ``first_steps(built, traffic, step, staged, batches, seed)``: drive the
    step through its first ``train.CHECK_STEPS`` batches; ``(what the
    program produced, state)``, the state going on into the window.
    ``reference_numbers(config, traffic, batches, seed, precision=...,
    fault=...)``: the plain reference's numbers for the same batches
    (``losses`` among them); ``precision`` and ``fault`` are the knobs of
    the control and the planted faults, and ``CONTROL_PRECISION`` and
    ``REFERENCE_FAULTS`` the values that ``limits.py`` reads a cell's upper
    readings with.
    ``train_numbers(program's, reference's)``: the numbers compared, a dict
    under the names that the cell's ``limits`` hold.
    ``step_work(config, traffic, batches)``: a step's work for the
    rooflines, ``ctx["work"]``, from the batches alone.
    ``exchange_left_out()`` (a family with cells on several chips): a
    context in which the program's exchanges between chips are left out.

``"kind": "serve"``
    ``serving_runtime(built, traffic["serve"])``: the program's runtime.
    ``serve_schedule(config, traffic, seed, seconds)``: every request of a
    window, with ``due_s``, ``offsets`` (request ``i`` holds samples
    ``offsets[i]:offsets[i+1]``), ``len()`` and ``request(i)``, the
    template that ``rt.warmup`` takes.
    ``requests_of(schedule)``: the requests as ``rt.submit`` takes them.
    ``reference_answers(config, schedule, picked, seed, precision=...)``:
    the plain reference's answers for the picked requests, in their order.
    ``serve_numbers(schedule, results, picked, answers)``: the numbers
    compared.
"""
