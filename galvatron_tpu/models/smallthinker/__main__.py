from galvatron_tpu.models.smallthinker import main

raise SystemExit(main())
