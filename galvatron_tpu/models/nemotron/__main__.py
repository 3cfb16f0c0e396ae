from galvatron_tpu.models.nemotron import main

raise SystemExit(main())
