"""The work a round needs, from a configuration's shapes alone:
``bench/work/<algorithm>_<learner>.py`` defines
``round_work(config, chips) -> {"bytes", "ops"}`` per chip."""
